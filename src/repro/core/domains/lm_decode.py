"""MCTS-guided LM decoding domain — the modern instantiation of the paper's
Playout stage (NN evaluation dominates; see DESIGN.md §2 assumption 1).

State = token buffer + length.  Actions = the top-A next tokens under the
policy LM.  Playout = greedy rollout of ``rollout_len`` tokens; reward =
exp(mean logprob) in (0, 1].  Priors = renormalized top-A policy probs (PUCT).

Two variants (DESIGN.md §10):

* ``LMDecodeDomain`` — generic (uncached): every step/playout re-evaluates
  the whole prefix.  Correct and simple, used by core tests and examples,
  and the parity oracle for the cached variant.
* ``CachedLMDecodeDomain`` — KV-cache-aware: the prompt is prefilled ONCE
  per search (at ``root_state``) and the per-sequence cache is threaded
  through the tree state, so every expand costs one incremental token and
  every playout ``rollout_len`` incremental tokens instead of full-prefix
  forwards.  Uses the family's ``prefill_fn``/``step_fn`` when implemented
  (dense: ``kernels/decode_attention``), else the pure-JAX fallback in
  ``models.base`` (correct for every family, just uncached).

The production serving path (repro.serving.mcts_decode) batches playouts
across lanes, which is exactly the paper's parallel-playout-stage load
balancing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.models.base import ModelConfig, get_family, seq_prefill, seq_step


def vocab_top_k(logits, k: int):
    """``jax.lax.top_k(logits, k)`` over the last axis, as ``(values,
    indices)``: the same values and indices, ties included."""
    return _folded_top_k(k)(logits)


@functools.cache
def _folded_top_k(k: int):
    # The TPU compiler lowers a rank-2 top_k to its TopK op but a rank-3
    # one (the search vmaps the top-A over lanes and slots) to a full sort
    # of the vocabulary, so every vmap folds its axis into the rows.
    @jax.custom_batching.custom_vmap
    def top(x):
        return tuple(jax.lax.top_k(x, k))

    @top.def_vmap
    def _fold(axis_size, in_batched, x):
        # one argument: vmap calls the rule only where x is batched
        vals, idx = top(x.reshape(-1, x.shape[-1]))
        lead = x.shape[:-1]
        return (vals.reshape(*lead, k), idx.reshape(*lead, k)), (True, True)

    return top


@dataclasses.dataclass(frozen=True)
class LMDecodeDomain:
    cfg: ModelConfig
    params: Any
    prompt: Any                       # [buf_len] int32 (padded buffer OK)
    num_actions: int = 4
    search_depth: int = 8             # max new tokens explored by the tree
    rollout_len: int = 4
    temperature: float = 1.0
    prompt_len: Any = None            # optional (traced) true prefix length;
                                      # None -> prompt.shape[0].  Lets batched
                                      # serving share one padded buffer shape
                                      # across requests of different lengths.
    root_warm: Any = None             # optional RootCarry (core.tree): seeds
                                      # the search root's N/W/prior from the
                                      # previous token's rerooted subtree
                                      # (cross-token reuse, DESIGN.md §12).
                                      # None searches cold.
    root_arena: Any = None            # optional carried TreeArena (same
                                      # capacity as the search's max_nodes):
                                      # the previous token's rerooted subtree,
                                      # spliced in wholesale (full subtree
                                      # reuse, DESIGN.md §14).  None (or
                                      # root_arena_alive False) searches cold.
    root_arena_alive: Any = None      # (traced) bool gating root_arena per
                                      # slot; None means alive.

    def __post_init__(self):
        object.__setattr__(self, "_fam", get_family(self.cfg))

    @property
    def max_len(self) -> int:
        return int(self.prompt.shape[0]) + self.search_depth + self.rollout_len

    def _plen(self):
        if self.prompt_len is None:
            return jnp.int32(self.prompt.shape[0])
        return jnp.asarray(self.prompt_len, jnp.int32)

    @jax.named_scope(scopes.ROOT)
    def root_state(self):
        toks = jnp.zeros((self.max_len,), jnp.int32)
        toks = jax.lax.dynamic_update_slice(toks, self.prompt.astype(jnp.int32), (0,))
        return {"toks": toks, "len": self._plen()}

    # -- internals ----------------------------------------------------------
    def _last_logits(self, toks, ln):
        logits = self._fam.logits_fn(self.cfg, self.params, toks[None])
        return logits[0, ln - 1].astype(jnp.float32) / self.temperature

    def _topk(self, state):
        logits = self._last_logits(state["toks"], state["len"])
        with jax.named_scope(scopes.TOPK):
            return vocab_top_k(logits, self.num_actions)

    # -- domain API ----------------------------------------------------------
    def step(self, state, action):
        _, top_toks = self._topk(state)
        tok = top_toks[action]
        toks = state["toks"].at[state["len"]].set(tok.astype(jnp.int32), mode="drop")
        return {"toks": toks, "len": state["len"] + 1}

    def is_terminal(self, state):
        return state["len"] >= self._plen() + self.search_depth

    def playout(self, state, rng):
        """Greedy rollout; reward = exp(mean next-token logprob)."""
        def body(c, _):
            toks, ln, acc = c
            logits = self._last_logits(toks, ln)
            logp = jax.nn.log_softmax(logits)
            tok = jnp.argmax(logits).astype(jnp.int32)
            acc = acc + logp[tok]
            toks = toks.at[ln].set(tok, mode="drop")
            return (toks, ln + 1, acc), None

        (_, _, acc), _ = jax.lax.scan(
            body, (state["toks"], state["len"], jnp.float32(0.0)),
            None, length=self.rollout_len)
        return jnp.exp(acc / self.rollout_len)

    def priors(self, state):
        top_vals, _ = self._topk(state)
        return jax.nn.softmax(top_vals)


@dataclasses.dataclass(frozen=True)
class CachedLMDecodeDomain(LMDecodeDomain):
    """KV-cache-aware variant: same decisions as ``LMDecodeDomain`` (up to
    float noise), amortized compute.

    State = ``{"len", "cache", "logits"}`` — the cache IS the token history
    (per-layer K/V rows for the dense family; a token buffer for the generic
    fallback) and ``logits`` are the next-token logits the prefix implies,
    so ``step``/``priors`` need no model call for the *current* position and
    each appended token costs one ``seq_step``.  The prompt is prefilled
    exactly once, in ``root_state`` — shared by every expand and playout of
    the search (the tree's structure-of-arrays state fans it out).

    Memory note: every tree node (and pipeline buffer lane) carries a full
    cache copy ``[L, max_len, Hkv, D]`` — the classic KV-cache trade of
    memory for compute, scaled here by tree capacity (DESIGN.md §10).

    Commit-time KV splice (DESIGN.md §12): when ``root_cache``/``root_logits``
    are set, ``root_state`` returns them verbatim instead of prefilling —
    the serving searcher advances the previous token's root row by one
    ``seq_step`` at commit time and splices it back in, so a request's
    prompt is prefilled once per *lifetime* instead of once per token.
    """

    root_cache: Any = None            # optional spliced root KV cache (must
                                      # match seq_prefill's layout at
                                      # max_len); None prefills the prompt
    root_logits: Any = None           # next-token logits paired with
                                      # root_cache

    @jax.named_scope(scopes.ROOT)
    def root_state(self):
        if self.root_cache is not None:
            return {"len": self._plen(), "cache": self.root_cache,
                    "logits": self.root_logits}
        toks = jnp.zeros((self.max_len,), jnp.int32)
        toks = jax.lax.dynamic_update_slice(toks, self.prompt.astype(jnp.int32), (0,))
        logits, cache = seq_prefill(self.cfg, self.params, toks, self._plen())
        return {"len": self._plen(), "cache": cache, "logits": logits}

    # -- internals ----------------------------------------------------------
    def _state_logits(self, state):
        return state["logits"].astype(jnp.float32) / self.temperature

    @jax.named_scope(scopes.TOPK)
    def _topk(self, state):
        return vocab_top_k(self._state_logits(state), self.num_actions)

    # -- domain API ----------------------------------------------------------
    def step(self, state, action):
        _, top_toks = self._topk(state)
        tok = top_toks[action].astype(jnp.int32)
        logits, cache = seq_step(self.cfg, self.params, state["cache"], tok,
                                 state["len"])
        return {"len": state["len"] + 1, "cache": cache, "logits": logits}

    def playout(self, state, rng):
        """Greedy rollout; reward = exp(mean next-token logprob).  Matches
        the uncached playout token-for-token: iteration t consumes the
        logits the previous step produced instead of a full forward."""
        def body(c, _):
            logits, cache, ln, acc = c
            scaled = logits.astype(jnp.float32) / self.temperature
            logp = jax.nn.log_softmax(scaled)
            tok = jnp.argmax(scaled).astype(jnp.int32)
            acc = acc + logp[tok]
            logits, cache = seq_step(self.cfg, self.params, cache, tok, ln)
            return (logits, cache, ln + 1, acc), None

        (_, _, _, acc), _ = jax.lax.scan(
            body, (state["logits"], state["cache"], state["len"],
                   jnp.float32(0.0)),
            None, length=self.rollout_len)
        return jnp.exp(acc / self.rollout_len)

    # is_terminal and priors are inherited: both consume only state["len"]
    # and _topk, which reads the cached logits.
