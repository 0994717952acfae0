"""MCTS-guided decoding on the unified ``repro.search`` API.

For each emitted token, a search (any registered strategy — default the
paper's pipeline) explores the top-A continuations: Select/Expand/Backup
walk the token tree while the Playout stage evaluates LM rollouts in
``lanes`` parallel lanes.  The chosen root action's token is committed and
the search restarts from the extended prefix.

Two granularities:

* ``mcts_decode``        — one request, one search per token (reference).
* ``mcts_decode_batch``  — B requests; every decode step is ONE device
  program that runs B independent searches via ``search_batch`` (batched
  multi-root search).  Requests share a padded token buffer; true prefix
  lengths ride along as ``LMDecodeDomain.prompt_len``, so the jitted step
  compiles once and is reused for every token of every request.

``make_batched_searcher`` is the factory behind both ``mcts_decode_batch``
and ``ServingEngine``'s MCTS-decode slots (DESIGN.md §5).

KV-cache-aware by default (``MCTSDecodeConfig.cached``): each slot's root
prefix is prefilled once per search via ``CachedLMDecodeDomain`` and the
per-slot cache rows live inside the per-token program, batch-sharded along
the slot axis exactly like ``buf``/``lens`` under a mesh (DESIGN.md §10).
Prompts may be ragged — they share one padded buffer shape with true
lengths riding along as ``prompt_len``.

Cross-token amortization (DESIGN.md §12) — the request-lifecycle rungs:

* ``kv_splice=True`` — commit-time KV splice: the searcher keeps each
  slot's root KV row + next-token logits in a carry, advances them by one
  ``seq_step`` when the token commits, and splices them into the next
  token's search root.  The prompt is prefilled once per request lifetime
  (at slot admission) instead of once per token.
* ``tree_reuse=True`` — cross-token subtree reuse: after committing a
  token the per-slot tree is rerooted on the chosen child
  (``core.tree.reroot``) and its N/W/children statistics seed the next
  search's root as warm-start priors instead of starting cold.

Either knob makes ``make_batched_searcher`` return a ``ReusableSearcher``
(explicit per-slot carry threaded through ``step``); with both off it
returns the stateless per-token function unchanged.

``MCTSDecodeConfig.wave_select`` picks the Select-stage iteration order of
every per-token search (lockstep = one batched UCT pass per tree level,
scan = lane-major; DESIGN.md §11).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.domains.lm_decode import CachedLMDecodeDomain, LMDecodeDomain
from repro.core.tree import init_tree, reroot, reroot_ok
from repro.models.base import ModelConfig, seq_prefill, seq_step
from repro.parallel.compat import (batch_sharding, global_batch_put,
                                   mesh_num_devices, replicated_sharding)
from repro.search import SearchConfig, SearchParams, search_keys
from repro.search.sharding import per_device


@dataclasses.dataclass(frozen=True)
class MCTSDecodeConfig:
    method: str = "pipeline"   # any registered strategy
    num_actions: int = 4
    budget: int = 32           # playouts per emitted token
    lanes: int = 4             # parallel playout stages
    search_depth: int = 8
    rollout_len: int = 4
    cp: float = 1.0
    temperature: float = 1.0
    # KV-cache-aware decode (DESIGN.md §10): each slot's prefix is prefilled
    # once per search and shared by all of that root's expands/playouts via
    # CachedLMDecodeDomain.  False restores the uncached domain (the parity
    # oracle, and a fallback for debugging numerics).
    cached: bool = True
    # Commit-time KV splice (DESIGN.md §12): carry each slot's advanced root
    # KV row across tokens and splice it into the next search instead of
    # re-prefilling.  Needs ``cached``; decisions are unchanged (prefill ==
    # prefill-then-step, the PR-4 parity invariant), only the per-token
    # prefill cost disappears.
    kv_splice: bool = False
    # Cross-token subtree reuse (DESIGN.md §14): reroot the arena on the
    # committed child — the whole surviving subtree (nodes, stats, cached
    # states) IS the next search's starting tree; abandoned rows are
    # recycled through the arena free-list.  Changes exploration
    # (deliberately) — leave off for bit-for-bit parity with cold per-token
    # searches.
    tree_reuse: bool = False
    # Select-stage iteration order inside each per-token search (DESIGN.md
    # §11/§14): "lockstep" descends all of a wave's lanes together with one
    # batched UCT pass per tree level; "scan" is the lane-major original;
    # "mega" fuses the whole wave into kernels/search_wave; "auto" follows
    # SearchParams' resolution.
    wave_select: str = "auto"
    # Kernel implementation for the accelerated paths ("auto" -> Pallas on
    # TPU); threaded into SearchParams.kernels (DESIGN.md §14).
    kernels: str = "auto"
    # In-flight decorrelation statistics inside each per-token search
    # (DESIGN.md §15): "loss" = classic virtual loss, "wu" = WU-UCT
    # unobserved counts (Q from completed playouts only).
    vl_mode: str = "loss"
    # Within-level lane assignment for the depth-major Select paths
    # (DESIGN.md §16): "independent" scores co-located lanes against an
    # identical board; "running" threads the running-assignment scan through
    # the batched level pass so same-parent lanes spread over distinct
    # continuations of the token tree.
    level_assign: str = "independent"
    # Arena capacity per slot for tree_reuse (0 -> 2*budget+2: one search's
    # worth of fresh allocations on top of a carried subtree).  The carry
    # must keep one capacity across tokens, so this is fixed per engine.
    arena_nodes: int = 0

    def __post_init__(self):
        if self.kv_splice and not self.cached:
            raise ValueError("kv_splice carries KV rows across tokens and "
                             "therefore requires cached=True")
        if self.tree_reuse and self.method == "root":
            raise ValueError(
                "tree_reuse reroots the search tree across tokens, but the "
                "'root' strategy keeps no shared tree (SearchResult.tree is "
                "None); pick a tree-bearing method")

    @property
    def stateful(self) -> bool:
        """True when decoding carries per-slot state across tokens."""
        return self.kv_splice or self.tree_reuse

    @property
    def resolved_arena_nodes(self) -> int:
        return self.arena_nodes or 2 * self.budget + 2

    def search_config(self) -> SearchConfig:
        return SearchConfig(
            method=self.method, budget=self.budget, lanes=self.lanes,
            keep_tree=self.tree_reuse,
            # tree_reuse pins every token's tree to ONE arena capacity so
            # the carried arena splices into the next search unchanged
            max_nodes=self.resolved_arena_nodes if self.tree_reuse else 0,
            kernels=self.kernels, wave_select=self.wave_select,
            vl_mode=self.vl_mode, level_assign=self.level_assign,
            params=SearchParams(cp=self.cp, max_depth=self.search_depth,
                                puct=True))


def _domain(cfg: ModelConfig, params, prompt, dcfg: MCTSDecodeConfig,
            prompt_len=None, **extra) -> LMDecodeDomain:
    cls = CachedLMDecodeDomain if dcfg.cached else LMDecodeDomain
    return cls(
        cfg=cfg, params=params, prompt=prompt,
        num_actions=dcfg.num_actions, search_depth=dcfg.search_depth,
        rollout_len=dcfg.rollout_len, temperature=dcfg.temperature,
        prompt_len=prompt_len, **extra)


def mcts_decode(cfg: ModelConfig, params, prompt: np.ndarray,
                n_tokens: int, dcfg: MCTSDecodeConfig, seed: int = 0
                ) -> List[int]:
    """Emit ``n_tokens`` tokens, each chosen by one search per token.

    Delegates to the B=1 batched path: the padded buffer + ``prompt_len``
    keep the searched shapes static, so the whole decode compiles once
    instead of re-jitting as the prefix grows.
    """
    prompt = np.asarray(prompt, np.int32).reshape(1, -1)
    return mcts_decode_batch(cfg, params, prompt, n_tokens, dcfg, seed)[0]


def _resolve_mesh(mesh, batch: int):
    """Shared mesh-resolution rule: None auto-shards real batch parallelism
    over all visible devices, False forces the single-device vmap."""
    if mesh is None and batch > 1 and jax.device_count() > 1:
        from repro.launch.mesh import make_search_mesh
        mesh = make_search_mesh()
    return None if mesh is False else mesh


class ReusableSearcher:
    """Batched per-token searcher with an explicit cross-token carry
    (DESIGN.md §12).  The carry is an opaque per-slot pytree:

    * ``"cache"``/``"logits"`` (``kv_splice``) — each slot's advanced root
      KV row and paired next-token logits, advanced by one ``seq_step``
      when a token commits;
    * ``"arena"``/``"action"``/``"alive"`` (``tree_reuse``) — each slot's
      full search arena from the previous token, the action it committed,
      and a liveness flag.  At the next step the arena is rerooted on the
      committed child (``core.tree.reroot`` — abandoned rows recycled
      through the free-list) and spliced in as the search's starting tree
      (``LMDecodeDomain.root_arena``); a dead/unreusable slot searches
      cold, bit-for-bit.

    Protocol (the engine's request lifecycle maps 1:1 onto it)::

        carry = s.init_carry(buf_len)            # engine start
        carry = s.admit(carry, slot, row, plen)  # request admitted: reset
                                                 # warm, prefill KV row once
        toks, carry = s.step(buf, lens, rng, carry)   # one token for all B

    ``admit`` is the ONLY place a prompt is prefilled; eviction needs no
    call (readmission overwrites the slot), which is exactly the eviction
    contract: a preempted request loses its carry and pays one re-prefill
    of prompt + committed tokens when readmitted.

    Multi-device: slots spread over a 1-D mesh exactly like the stateless
    searcher — every carry leaf is sharded along its leading slot axis
    (DESIGN.md §9); the batch is padded to a device-count multiple and the
    pad rows ride along as permanently-dead slots.
    """

    def __init__(self, cfg: ModelConfig, params, dcfg: MCTSDecodeConfig,
                 batch: int, mesh=None):
        self.cfg, self.dcfg, self.batch = cfg, dcfg, batch
        self.mesh = mesh
        ndev = mesh_num_devices(mesh) if mesh is not None else 1
        self.padded = batch + ((-batch) % ndev)
        self.scfg = dcfg.search_config()
        # the weights are an argument of every program, never a constant
        # baked into it (_place_weights)
        self.params = _place_weights(params, mesh)
        if mesh is None:
            self._jstep = jax.jit(self._step_impl)
            self._jadmit = jax.jit(self._admit_impl)
        else:
            shard, repl = batch_sharding(mesh), replicated_sharding(mesh)
            self._jstep = jax.jit(
                per_device(self._step_impl, mesh, 5, replicated=(0,)),
                in_shardings=(repl, shard, shard, shard, shard),
                out_shardings=(shard, shard))
            self._jadmit = jax.jit(
                per_device(self._admit_local, mesh, 5,
                           replicated=(0, 2, 3, 4)),
                out_shardings=shard)

    # -- carry lifecycle ----------------------------------------------------
    def init_carry(self, buf_len: int):
        """Identity carry for ``padded`` slots sharing a ``[*, buf_len]``
        token buffer: dead (all-zero) arenas — ``alive`` is False until the
        first search fills them, so every slot's first token searches cold,
        bit-for-bit — and zeroed KV rows (dead until ``admit`` prefills)."""
        d = self.dcfg
        carry = {}
        if d.tree_reuse:
            dummy = _domain(self.cfg, self.params,
                            jnp.zeros((buf_len,), jnp.int32), d,
                            prompt_len=jnp.int32(1))
            shapes = jax.eval_shape(
                lambda: init_tree(dummy, d.resolved_arena_nodes))
            carry["arena"] = jax.tree_util.tree_map(
                lambda s: jnp.zeros((self.padded,) + s.shape, s.dtype),
                shapes)
            carry["action"] = jnp.zeros((self.padded,), jnp.int32)
            carry["alive"] = jnp.zeros((self.padded,), bool)
        if d.kv_splice:
            max_len = buf_len + d.search_depth + d.rollout_len
            lg, cache = jax.eval_shape(
                lambda: seq_prefill(self.cfg, self.params,
                                    jnp.zeros((max_len,), jnp.int32),
                                    jnp.int32(1)))
            carry["logits"] = jnp.zeros((self.padded,) + lg.shape, lg.dtype)
            carry["cache"] = jax.tree_util.tree_map(
                lambda s: jnp.zeros((self.padded,) + s.shape, s.dtype), cache)
        return carry

    def admit(self, carry, slot, buf_row, plen):
        """Reset slot ``slot`` for a fresh request whose padded prefix is
        ``buf_row`` with true length ``plen``: warm stats back to identity,
        KV row prefilled ONCE (the request's only prefill)."""
        return self._jadmit(self.params, carry, jnp.int32(slot),
                            jnp.asarray(buf_row, jnp.int32),
                            jnp.int32(plen))

    def _admit_local(self, params, carry, slot, buf_row, plen):
        """``_admit_impl`` on one device's rows of a mesh-sharded carry:
        every device prefills (in parallel), the one holding ``slot``
        keeps the row."""
        rows = jax.tree_util.tree_leaves(carry)[0].shape[0]
        local = slot - jax.lax.axis_index(self.mesh.axis_names[0]) * rows
        local = jnp.where((local >= 0) & (local < rows), local, rows)
        return self._admit_impl(params, carry, local, buf_row, plen)

    def _admit_impl(self, params, carry, slot, buf_row, plen):
        """Out-of-range ``slot`` rows are dropped (``_admit_local``)."""
        d = self.dcfg
        new = dict(carry)
        if d.tree_reuse:
            # killing the liveness flag IS the reset: a dead slot's next
            # search starts cold and overwrites the stale arena wholesale
            new["alive"] = carry["alive"].at[slot].set(False, mode="drop")
        if d.kv_splice:
            max_len = buf_row.shape[0] + d.search_depth + d.rollout_len
            toks = jnp.zeros((max_len,), jnp.int32)
            toks = jax.lax.dynamic_update_slice(toks, buf_row, (0,))
            logits, cache = seq_prefill(self.cfg, params, toks, plen)
            new["cache"] = jax.tree_util.tree_map(
                lambda full, one: full.at[slot].set(one, mode="drop"),
                carry["cache"], cache)
            new["logits"] = carry["logits"].at[slot].set(logits, mode="drop")
        return new

    # -- per-token step -----------------------------------------------------
    def _args(self, buf, lens, rng, carry):
        buf, lens = _pad_slots(buf, lens, self.padded - self.batch)
        return (self.params, buf, lens, jax.random.split(rng, self.padded),
                carry)

    def step(self, buf, lens, rng, carry):
        """One batched multi-root search over all slots -> each slot's
        chosen token, plus the carry advanced by the committed tokens."""
        toks, carry = self._jstep(*self._args(buf, lens, rng, carry))
        return toks[:self.batch], carry

    def lower(self, buf, lens, rng, carry):
        """The per-token program ``step`` runs, lowered."""
        return self._jstep.lower(*self._args(buf, lens, rng, carry))

    def _step_impl(self, params, buf, lens, keys, carry):
        """Per-slot keys; under a mesh this runs on each device's own slots
        (so every batch size here is ``buf.shape[0]``)."""
        cfg, d = self.cfg, self.dcfg
        rows = buf.shape[0]
        if d.tree_reuse:
            # reroot every slot's arena on its committed action (recycling
            # the abandoned rows); a slot is reusable only if it is alive
            # AND the committed child was actually expanded last search
            use = carry["alive"] & jax.vmap(reroot_ok)(
                carry["arena"], carry["action"])
            ar = jax.vmap(reroot)(carry["arena"], carry["action"])
        domains = []
        for i in range(rows):
            kw = {}
            if d.kv_splice:
                kw["root_cache"] = jax.tree_util.tree_map(
                    lambda x: x[i], carry["cache"])
                kw["root_logits"] = carry["logits"][i]
            dom = _domain(cfg, params, buf[i], d, prompt_len=lens[i], **kw)
            if d.tree_reuse:
                ar_i = jax.tree_util.tree_map(lambda x: x[i], ar)
                # carried terminal flags reflect the PREVIOUS horizon
                # (len >= plen + depth, and plen just advanced) — refresh
                # them against this token's domain
                ar_i = ar_i.replace(
                    terminal=jax.vmap(dom.is_terminal)(ar_i.state))
                dom = dataclasses.replace(
                    dom, root_arena=ar_i, root_arena_alive=use[i])
            domains.append(dom)
        res = search_keys(domains, self.scfg, keys)
        if d.kv_splice:
            # the carried logits ARE the root's next-token distribution
            tops = jax.vmap(
                lambda lg: jax.lax.top_k(lg, d.num_actions)[1])(
                carry["logits"])
        else:
            def root_topk(buf_row, len_row):
                dom = _domain(cfg, params, buf_row, d, prompt_len=len_row)
                _, top = dom._topk(dom.root_state())
                return top
            tops = jax.vmap(root_topk)(buf, lens)
        toks = tops[jnp.arange(rows), res.best_action].astype(jnp.int32)
        new = dict(carry)
        if d.tree_reuse:
            # the searched arenas + committed actions ARE the carry; the
            # reroot happens lazily at the START of the next step
            new["arena"] = res.tree
            new["action"] = res.best_action.astype(jnp.int32)
            new["alive"] = jnp.ones((rows,), bool)
        if d.kv_splice:
            # advance each root row by the committed token (ONE step, vs a
            # whole-prefix prefill on the cold path)
            logits, cache = jax.vmap(
                lambda c, t, p: seq_step(cfg, params, c, t, p))(
                carry["cache"], toks, lens)
            new["cache"], new["logits"] = cache, logits
        return toks, new


def _place_weights(params, mesh):
    """Weights ride into the per-token programs as an argument — closed
    over, jit would bake them into every executable as constants (a second
    copy on the device and a far longer compile).  Under a mesh they are
    replicated onto every device once, here."""
    if mesh is None:
        return params
    repl = replicated_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: global_batch_put(x, repl),
                                  params)


def _pad_slots(buf, lens, extra: int):
    """Pad dead rows (len 0 == empty slot: searched, output ignored) up to
    the padded batch a mesh needs."""
    buf = jnp.asarray(buf, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    if extra:
        buf = jnp.concatenate(
            [buf, jnp.zeros((extra, buf.shape[1]), buf.dtype)])
        lens = jnp.concatenate([lens, jnp.zeros((extra,), lens.dtype)])
    return buf, lens


class BatchedSearcher:
    """The stateless per-token searcher: ``(token_buf [B, buf_len] i32,
    lens [B] i32, rng) -> [B] i32``, one jitted device program that takes
    the weights as an argument.  ``lower`` exposes that program."""

    def __init__(self, jstep, params, batch: int, padded: int):
        self._jstep, self.params = jstep, params
        self.batch, self.padded = batch, padded

    def _args(self, buf, lens, rng):
        buf, lens = _pad_slots(buf, lens, self.padded - self.batch)
        return self.params, buf, lens, jax.random.split(rng, self.padded)

    def __call__(self, buf, lens, rng):
        return self._jstep(*self._args(buf, lens, rng))[:self.batch]

    def lower(self, buf, lens, rng):
        return self._jstep.lower(*self._args(buf, lens, rng))


def make_batched_searcher(cfg: ModelConfig, params, dcfg: MCTSDecodeConfig,
                          batch: int, mesh=None):
    """Factory for the per-token batched searcher.

    Stateless (default): returns a ``BatchedSearcher``, ``(token_buf
    [B, buf_len] i32, lens [B] i32, rng) -> [B] i32`` — one jitted device
    program that searches all B prefixes cold and returns each slot's
    chosen next token.  Shapes are static, so one compilation serves every
    decode step.

    Stateful (``dcfg.kv_splice`` or ``dcfg.tree_reuse``): returns a
    ``ReusableSearcher`` whose ``step`` additionally threads the per-slot
    cross-token carry (spliced KV rows / rerooted subtree stats).

    Multi-device: pass ``mesh`` (1-D, from ``make_search_mesh``) — or rely on
    the default, which shards automatically when more than one device is
    visible — and the searched batch is padded up to a multiple of the device
    count and split along the batch axis, spreading live slots across the
    mesh (DESIGN.md §9).  Pass ``mesh=False`` to force single-device vmap.
    Padded rows consume their own rng splits, so with a mesh the sampled
    token stream differs from the unsharded searcher (same distribution).
    """
    mesh = _resolve_mesh(mesh, batch)
    if dcfg.stateful:
        return ReusableSearcher(cfg, params, dcfg, batch, mesh=mesh)

    scfg = dcfg.search_config()
    ndev = mesh_num_devices(mesh) if mesh is not None else 1
    padded = batch + ((-batch) % ndev)

    def step(params, buf, lens, keys):
        """Per-slot keys; under a mesh each device runs its own slots."""
        def root_topk(buf_row, len_row):
            d = _domain(cfg, params, buf_row, dcfg, prompt_len=len_row)
            _, top = d._topk(d.root_state())
            return top

        rows = buf.shape[0]
        domains = [_domain(cfg, params, buf[i], dcfg, prompt_len=lens[i])
                   for i in range(rows)]
        res = search_keys(domains, scfg, keys)
        tops = jax.vmap(root_topk)(buf, lens)            # [rows, A], one pass
        return tops[jnp.arange(rows), res.best_action].astype(jnp.int32)

    if mesh is None:
        jstep = jax.jit(step)
    else:
        # buf/lens/keys split along the slot axis, one shard per device;
        # the weights are replicated
        shard = batch_sharding(mesh)
        repl = replicated_sharding(mesh)
        jstep = jax.jit(per_device(step, mesh, 4, replicated=(0,)),
                        in_shardings=(repl, shard, shard, shard),
                        out_shardings=shard)
    return BatchedSearcher(jstep, _place_weights(params, mesh), batch,
                           padded)


def _pad_prompts(prompts, n_tokens: int):
    """Normalize equal-length [B, plen] or ragged list-of-sequences prompts
    into (padded buffer [B, max_plen + n_tokens] i32, true lengths [B] i32).
    """
    if isinstance(prompts, (list, tuple)):
        rows = [np.asarray(p, np.int32) for p in prompts]
        if any(r.ndim != 1 for r in rows):
            raise ValueError("ragged prompts must be a list of 1-D token "
                             f"sequences, got ndims {[r.ndim for r in rows]}")
    else:
        arr = np.asarray(prompts, np.int32)   # np or jax array-likes
        if arr.ndim != 2:
            raise ValueError("prompts must be [B, plen] or a (ragged) list "
                             f"of 1-D sequences, got shape {arr.shape}")
        rows = list(arr)
    if not rows:
        raise ValueError("prompts must contain at least one request")
    lens = np.array([len(r) for r in rows], np.int32)
    if (lens == 0).any():
        raise ValueError("every prompt needs at least one token, got "
                         f"lengths {lens.tolist()}")
    buf = np.zeros((len(rows), int(lens.max()) + n_tokens), np.int32)
    for i, r in enumerate(rows):
        buf[i, : len(r)] = r
    return buf, lens


def mcts_decode_batch(cfg: ModelConfig, params, prompts,
                      n_tokens: int, dcfg: MCTSDecodeConfig, seed: int = 0,
                      mesh=None) -> List[List[int]]:
    """Decode B prompts together: each of the ``n_tokens`` steps is a single
    batched multi-root search over all requests.

    ``prompts`` is [B, plen] int32 OR a ragged list of 1-D token sequences:
    requests are padded to one buffer shape and their true lengths ride
    along as ``LMDecodeDomain.prompt_len``, so mixed-length batches compile
    to the same single program as equal-length ones.  ``mesh`` as in
    ``make_batched_searcher``: None auto-shards the searched batch over
    multiple devices, False forces single-device vmap.

    With ``dcfg.kv_splice``/``dcfg.tree_reuse`` the per-request carry is
    threaded across the token loop: every prompt is prefilled once up front
    and each committed token costs one incremental step (DESIGN.md §12).
    """
    buf, lens = _pad_prompts(prompts, n_tokens)
    b = buf.shape[0]
    searcher = make_batched_searcher(cfg, params, dcfg, batch=b, mesh=mesh)
    rng = jax.random.key(seed)
    out: List[List[int]] = [[] for _ in range(b)]
    carry = None
    if dcfg.stateful:
        carry = searcher.init_carry(buf.shape[1])
        for i in range(b):
            carry = searcher.admit(carry, i, buf[i], lens[i])
    for _ in range(n_tokens):
        rng, sub = jax.random.split(rng)
        if dcfg.stateful:
            toks, carry = searcher.step(buf, lens, sub, carry)
            toks = np.asarray(toks)
        else:
            toks = np.asarray(
                searcher(jnp.asarray(buf), jnp.asarray(lens), sub))
        for i in range(b):
            out[i].append(int(toks[i]))
            buf[i, lens[i]] = toks[i]
        lens += 1
    return out
