"""Continuous-batching serving engine (prefill/decode interleave).

Host-side orchestration over the jitted ``prefill``/``decode_step`` of any
arch in the zoo: a fixed pool of ``max_batch`` decode slots; finished or
empty slots are refilled by prefilling queued requests into the batch
position (per-slot KV cache rows + per-slot positions), so decode steps
always run at full batch — the serving-side analogue of keeping the paper's
pipeline stages busy.

Request lifecycle (DESIGN.md §12): admission order, per-slot budgets and
priority preemption live in ``serving.scheduler.RequestScheduler``; the
engine owns device state (KV rows, prefix buffers, search carry) and reacts
to the scheduler's ``Admit``/``Evict`` events.  ``ServingStats`` records
the lifecycle timings (queue wait, TTFT, per-token gaps, latency) and
engine counters; ``run_until_drained`` returns its per-request summaries
and ``ServingEngine.stats.snapshot()`` is a flat wandb-ready dict.

Host spans (``jax.profiler.TraceAnnotation``; one inactive ``TraceMe`` each
when no profiler runs) mark the engine's phases in a profile, on the device
ops' clock: ``serving.step`` around ``step``, ``serving.admit`` around each
admission or eviction (with the request's ``uid`` and ``slot``), and in
search decoding ``serving.search`` (dispatch of the per-token program),
``serving.sync`` (the one transfer of its tokens and root counters) and
``serving.commit`` (the per-slot bookkeeping).

Two per-slot decode modes (EngineConfig.decode):

* ``"greedy"`` — KV-cached argmax decoding (the seed behaviour).
* ``"mcts"``   — every engine step runs ONE batched multi-root search
  (repro.search.search_batch via make_batched_searcher) over all live
  slots' prefixes and commits each slot's chosen token: the paper's search
  as a serving feature, one device program per emitted token across the
  whole batch (DESIGN.md §5).  KV-cache-aware by default
  (``MCTSDecodeConfig.cached``): inside that program each slot gets its own
  cache row, prefilled once per search and shared by every playout of that
  root; with ``EngineConfig.mesh`` the rows shard along the slot axis like
  the prefix buffer (DESIGN.md §10).  With ``MCTSDecodeConfig.kv_splice`` /
  ``tree_reuse`` the searcher is the stateful ``ReusableSearcher`` and the
  engine threads its per-slot carry through admissions and steps: prompts
  prefill once per request lifetime and committed subtrees warm-start the
  next token's search (DESIGN.md §12).  The searches' Select-stage
  iteration order follows ``MCTSDecodeConfig.wave_select`` (lockstep = one
  batched UCT pass per tree level; DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.base import ModelConfig, get_family
from repro.serving.mcts_decode import (BatchedSearcher, MCTSDecodeConfig,
                                       ReusableSearcher,
                                       make_batched_searcher, unpack)
from repro.serving.scheduler import (Admit, Evict, Request, RequestScheduler)
from repro.serving.stats import ServingStats, percentile


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 4
    max_seq: int = 256
    eos_token: int = -1                # -1: never stops early
    decode: str = "greedy"             # "greedy" | "mcts"
    policy: str = "fcfs"               # admission policy: "fcfs" | "spf"
    mcts: Optional[MCTSDecodeConfig] = None   # knobs for decode="mcts"
    # decode="mcts" device mesh: None auto-shards the per-step batched search
    # across all visible devices (live slots spread over a 1-D mesh, DESIGN.md
    # §9); False pins it to one device; or pass an explicit 1-D mesh.
    mesh: Any = None


class ServingEngine:
    """Single-host continuous batching over jitted model steps."""

    def __init__(self, cfg: ModelConfig, params, engine_cfg: EngineConfig,
                 stats: Optional[ServingStats] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = engine_cfg
        self.fam = get_family(cfg)
        b, s = engine_cfg.max_batch, engine_cfg.max_seq
        self.stats = stats if stats is not None else ServingStats()
        self.sched = RequestScheduler(b, policy=engine_cfg.policy)
        # the persistent [L, B, S, ...] cache backs the greedy path; mcts
        # mode's per-slot cache rows live inside the per-token search
        # program instead (prefilled from prefix_buf, DESIGN.md §10)
        self.cache = (self.fam.init_cache(cfg, b, s)
                      if engine_cfg.decode == "greedy" else None)
        self._decode = jax.jit(
            lambda p, c, t: self.fam.decode_step(cfg, p, c, t))
        self._prefill_one = jax.jit(
            lambda p, t, c: self.fam.prefill(cfg, p, t, c))
        self.mode = engine_cfg.decode
        self._carry = None
        if self.mode == "mcts":
            self.mcfg = engine_cfg.mcts or MCTSDecodeConfig()
            # per-slot padded prefix buffers; true lengths ride separately so
            # the batched searcher keeps one static shape for all steps
            self.prefix_buf = np.zeros((b, s), np.int32)
            self.prefix_len = np.zeros((b,), np.int32)
            self._rng = jax.random.key(0)
            self._mcts_search = make_batched_searcher(
                cfg, params, self.mcfg, batch=b, mesh=engine_cfg.mesh)
            if isinstance(self._mcts_search, ReusableSearcher):
                self._carry = self._mcts_search.init_carry(s)
        elif self.mode != "greedy":
            raise ValueError(f"unknown decode mode {engine_cfg.decode!r}")

    # -- request intake ----------------------------------------------------
    @property
    def slots(self) -> List[Optional[Request]]:
        """Last request seen by each slot (live or just-finished)."""
        return self.sched.slots

    def submit(self, req: Request):
        if len(req.prompt) > self.ecfg.max_seq:
            raise ValueError(
                f"prompt of request {req.uid} has {len(req.prompt)} tokens, "
                f"exceeding max_seq={self.ecfg.max_seq}")
        req.enqueue_t = self.stats.now()
        self.stats.on_submit(req.uid, req.enqueue_t)
        self.sched.submit(req)

    def pending(self) -> int:
        return self.sched.pending()

    # -- scheduler event handlers -------------------------------------------
    def _admit_loop(self):
        """Apply scheduler events until quiescent.  Admissions that finish
        immediately (zero budget, prefill EOS, capacity) retire their slot,
        which can unblock another admission — hence the loop."""
        while True:
            events = self.sched.schedule()
            if not events:
                return
            for ev in events:
                if isinstance(ev, Evict):
                    self._on_evict(ev.slot, ev.req)
                else:
                    self._on_admit(ev.slot, ev.req)

    def _on_evict(self, i: int, req: Request):
        """Eviction contract (DESIGN.md §12): device state is simply dropped
        — the prefix buffer row is zeroed and any searcher carry row goes
        stale (readmission overwrites it via ``admit``).  The request keeps
        its committed tokens; readmission re-prefills prompt + out_tokens."""
        with TraceAnnotation("serving.admit", uid=req.uid, slot=i):
            self.stats.on_preempt(req.uid, self.stats.now())
            if self.mode == "mcts":
                self.prefix_buf[i] = 0
                self.prefix_len[i] = 0
            # greedy: the KV row is dead weight until the slot is refilled

    def shrink(self, lost_slots) -> List[int]:
        """Elastic shrink event (DESIGN.md §13): a lost host's slots are
        evicted-and-requeued through the scheduler — victims keep their
        committed tokens and FCFS position, exactly like priority preemption
        — and removed from the admission pool for good.  Surviving slots are
        refilled immediately, so the engine keeps serving at the shrunken
        batch.  Returns the slots that actually held a live request."""
        lost = sorted({int(s) for s in lost_slots})
        newly = [s for s in lost if not self.sched.is_disabled(s)]
        if self.sched.num_enabled() - len(newly) < 1:
            raise ValueError("shrink would disable every slot; at least one "
                             "must survive to keep serving")
        evicted = []
        for s in lost:
            ev = self.sched.evict(s)
            if ev is not None:
                self._on_evict(ev.slot, ev.req)
                evicted.append(s)
        self.sched.disable(lost)
        self._admit_loop()
        return evicted

    def _finish(self, i: int, req: Request):
        req.done = True
        req.finish_t = self.stats.now()
        self.stats.on_finish(req.uid, req.finish_t)
        self.sched.retire(i)

    def _on_admit(self, i: int, req: Request):
        with TraceAnnotation("serving.admit", uid=req.uid, slot=i):
            self._admit(i, req)

    def _admit(self, i: int, req: Request):
        self.stats.on_admit(req.uid, self.stats.now())
        if req.budget_left <= 0:
            # nothing to decode: finish without touching device state
            self._finish(i, req)
            return
        # effective prefix = prompt + committed tokens (preemption round-trip)
        prefix = np.asarray(list(req.prompt) + req.out_tokens, np.int32)
        plen = len(prefix)
        if self.mode == "mcts":
            # no host-side KV prefill on the cold path: the searcher prefills
            # this slot's cache row from the prefix buffer inside each
            # per-token program (zeroing the buffer row is the slot reset).
            # Stateful searchers prefill ONCE here instead (KV splice).
            self.prefix_buf[i] = 0
            self.prefix_buf[i, :plen] = prefix
            self.prefix_len[i] = plen
            if self._carry is not None:
                self._carry = self._mcts_search.admit(
                    self._carry, i, self.prefix_buf[i], plen)
            return
        # greedy: prefill this request alone, splice its cache row into slot i
        one_cache = self.fam.init_cache(self.cfg, 1, self.ecfg.max_seq)
        logits, one_cache = self._prefill_one(
            self.params, jnp.asarray(prefix, jnp.int32)[None], one_cache)
        tok = int(jnp.argmax(logits[0, -1]))
        req.out_tokens.append(tok)
        self.stats.on_token(req.uid, self.stats.now())
        self.sched.on_token(i)
        # each decode step writes one KV entry at position plen, plen+1,
        # ... — clamp so the slot finishes before scattering past max_seq
        self.sched.cap_remaining(i, self.ecfg.max_seq - plen)
        self.cache = jax.tree_util.tree_map(
            lambda full, one: full.at[_batch_axis_index(full, i)].set(
                one[_one_index(one)]),
            self.cache, one_cache)
        if self.sched.exhausted(i) or tok == self.ecfg.eos_token:
            self._finish(i, req)

    def _next_tokens(self) -> jnp.ndarray:
        toks = np.zeros((self.ecfg.max_batch, 1), np.int32)
        for i in self.sched.live():
            req = self.sched.request(i)
            if req.out_tokens:
                toks[i, 0] = req.out_tokens[-1]
        return jnp.asarray(toks)

    # -- main loop ----------------------------------------------------------
    def step(self):
        """One decode step over all live slots.  Slots freed mid-step (EOS,
        budget, capacity) are refilled before returning, so the NEXT step
        already decodes the replacement — no idle step in between."""
        with TraceAnnotation("serving.step"):
            self._admit_loop()
            live = self.sched.live()
            if not live:
                return 0
            if self.mode == "mcts":
                emitted = self._mcts_step(live)
                self.stats.on_step(emitted, searched=len(live))
            else:
                emitted = self._greedy_step(live)
                self.stats.on_step(emitted)
            self._admit_loop()      # refill freed slots in the same step
            return emitted

    def _greedy_step(self, live: List[int]) -> int:
        logits, self.cache = self._decode(self.params, self.cache,
                                          self._next_tokens())
        toks = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        now = self.stats.now()
        for i in live:
            req = self.sched.request(i)
            tok = int(toks[i])
            req.out_tokens.append(tok)
            self.stats.on_token(req.uid, now)
            self.sched.on_token(i)
            if self.sched.exhausted(i) or tok == self.ecfg.eos_token:
                self._finish(i, req)
        return len(live)

    def _mcts_step(self, live: List[int]) -> int:
        """One batched multi-root search over every slot; commit one token
        per live slot, with the search root's counters.  Dead slots are
        searched too (the program is one fixed [B]-batch) and their outputs
        ignored."""
        with TraceAnnotation("serving.search"):
            self._rng, sub = jax.random.split(self._rng)
            out, packed = self._dispatch(sub)
        with TraceAnnotation("serving.sync"):
            out = np.asarray(out)
            toks, counters = unpack(out) if packed else (out, None)
        with TraceAnnotation("serving.commit"):
            now = self.stats.now()
            for i in live:
                req = self.sched.request(i)
                tok = int(toks[i])
                req.out_tokens.append(tok)
                if counters is not None:
                    req.root_visits.append(counters["root_visits"][i])
                    req.root_values.append(counters["root_values"][i])
                self.stats.on_token(req.uid, now)
                at_capacity = self.prefix_len[i] >= self.ecfg.max_seq
                if not at_capacity:
                    self.prefix_buf[i, self.prefix_len[i]] = tok
                    self.prefix_len[i] += 1
                self.sched.on_token(i)
                # finish at the sequence capacity too — further searches
                # would keep emitting from the same frozen prefix
                if (self.sched.exhausted(i) or tok == self.ecfg.eos_token
                        or at_capacity):
                    self._finish(i, req)
            if counters is not None:
                self.stats.on_search(
                    int(counters["playouts"][live].sum()),
                    int(counters["duplicates"][live].sum()))
        return len(live)

    def _dispatch(self, rng):
        """Launch the per-token program without waiting for it.  Returns its
        one device output and whether that holds the root's counters beside
        the tokens (``unpack``): a per-token callable other than the
        searchers returns tokens alone."""
        s, buf, lens = self._mcts_search, self.prefix_buf, self.prefix_len
        if self._carry is not None:
            out, self._carry = s.search(buf, lens, rng, self._carry)
            return out, True
        if isinstance(s, BatchedSearcher):
            return s.search(buf, lens, rng), True
        return s(jnp.asarray(buf), jnp.asarray(lens), rng), False

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[str, Any]:
        emitted = 0
        steps = 0
        while steps < max_steps:
            e = self.step()
            steps += 1
            emitted += e
            if e == 0 and self.sched.pending() == 0:
                break
        reqs = self.stats.request_summaries()
        lats = [r["latency"] for r in reqs.values()
                if r["latency"] is not None]
        return {
            "steps": steps,
            "tokens": emitted,
            "requests": reqs,
            "latency_p50": percentile(lats, 50) if lats else 0.0,
            "latency_p95": percentile(lats, 95) if lats else 0.0,
            "stats": self.stats.snapshot(),
        }


def _batch_axis_index(full, i):
    """Index tuple selecting batch row i (batch axis differs per cache leaf)."""
    # conventions: leaves are [L, B, ...] (stacked) or [B] (pos)
    if full.ndim == 1:
        return (i,)
    return (slice(None), i)


def _one_index(one):
    if one.ndim == 1:
        return (0,)
    return (slice(None), 0)
