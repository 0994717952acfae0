"""The five built-in strategies, registered under their paper names.

Each is the canonical implementation (the old ``core.run_*`` entry points
are now deprecated shims over these).  All share:

* ``cfg.lanes`` as the single degree-of-parallelism knob (pipeline lanes ==
  tree-parallel threads == root/leaf workers);
* the common stats schema (api.STATS_KEYS), with ``playouts_requested`` the
  budget after lane rounding and ``playouts_completed`` the backups actually
  applied — the pipeline counts completions per tick, the others complete
  exactly what they request.  ``duplicates`` means exactly one thing for
  every strategy: the selected leaf already had in-flight playouts when the
  lane arrived (pre-wave in-flight count > 0, or a lower-numbered lane of
  the same wave picked the same leaf).  Single-trajectory strategies
  (sequential / root / leaf) measure the same event — it is provably always
  zero for them, and tests assert that;
* ``SearchResult`` assembly via ``api.result_from_tree``.

Paper mapping (§IV baselines + §V contribution):
  sequential — Fig. 1 S→E→P→B loop (strength reference)
  root       — Ensemble UCT: independent trees, root stats summed
  leaf       — one trajectory, ``lanes`` parallel playouts from its leaf
  tree       — shared tree + virtual loss, ``lanes`` trajectories per round
  pipeline   — the paper's software-pipelined MCTS (linear/nonlinear)

``tree`` and ``pipeline`` waves select through ``core.stages.select_wave``,
so ``SearchParams.wave_select`` switches their Select stage between the
lane-major scan and the depth-major lockstep path (one batched UCT pass per
tree level — DESIGN.md §11) without touching this module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core import stages as S
from repro.core.tree import init_tree, root_child_stats
from repro.search.api import (SearchConfig, SearchResult, make_stats,
                              register_strategy, result_from_tree)

__all__ = ["PIPE_STAGES", "sequential", "root", "leaf", "tree_parallel",
           "pipeline"]

PIPE_STAGES = 4          # S, E, P, B


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _sequential_core(domain, sp, budget: int, max_nodes: int, rng):
    """Shared S→E→P→B loop; returns (tree, playout values, dup flags)."""
    tree = init_tree(domain, max_nodes or budget + 2)

    def it(tree, rng_t):
        tree, sel = S.select_one(tree, sp, jnp.asarray(True))
        tree, exp = S.expand_one(tree, domain, sp, sel)
        po = S.playout_wave(
            domain, sp,
            jax.tree_util.tree_map(lambda x: x[None], exp), rng_t)
        tree = S.backup_wave(tree, po, sp)
        return tree, (po["value"][0], sel["dup"])

    tree, (values, dups) = jax.lax.scan(
        it, tree, jax.random.split(rng, budget))
    return tree, values, dups


@register_strategy("sequential")
def sequential(domain, cfg: SearchConfig, rng) -> SearchResult:
    tree, values, dups = _sequential_core(domain, cfg.params, cfg.budget,
                                          cfg.max_nodes, rng)
    # one trajectory in flight at a time -> dups.sum() is provably 0, but
    # report the measured event so all strategies share one definition
    stats = make_stats(cfg.budget, cfg.budget, dups.sum(), cfg.budget)
    return result_from_tree(tree, stats, extras={"values": values})


@register_strategy("root")
def root(domain, cfg: SearchConfig, rng) -> SearchResult:
    """Root parallelization / Ensemble UCT (Chaslot; Fern & Lewis):
    ``lanes`` independent sequential searches, root statistics summed.  No
    single shared tree exists, so ``SearchResult.tree`` is None."""
    workers = max(cfg.lanes, 1)
    per = _ceil_div(cfg.budget, workers)

    def one(r):
        tree, _, dups = _sequential_core(domain, cfg.params, per,
                                         cfg.max_nodes, r)
        n, w, _ = root_child_stats(tree)    # n already 0 at invalid slots
        return n.astype(jnp.int32), w, dups.sum()

    ns, ws, dups = jax.vmap(one)(jax.random.split(rng, workers))
    visits, value = ns.sum(0), ws.sum(0)
    best = jnp.argmax(jnp.where(visits > 0, visits, -1)).astype(jnp.int32)
    stats = make_stats(per * workers, per * workers, dups.sum(), per)
    return SearchResult(action_visits=visits, action_value=value,
                        best_action=best, tree=None, stats=stats, extras={})


@register_strategy("leaf")
def leaf(domain, cfg: SearchConfig, rng) -> SearchResult:
    """Leaf parallelization (Chaslot et al.): sequential S/E, ``lanes``
    playouts from the selected leaf per iteration, aggregate backup."""
    sp, workers = cfg.params, max(cfg.lanes, 1)
    iters = _ceil_div(cfg.budget, workers)
    tree = init_tree(domain, cfg.max_nodes or iters + 2)

    def it(tree, rng_t):
        tree, sel = S.select_one(tree, sp, jnp.asarray(True))
        tree, exp = S.expand_one(tree, domain, sp, sel)
        with jax.named_scope(scopes.PLAYOUT):
            values = jax.vmap(lambda r: domain.playout(exp["state"], r))(
                jax.random.split(rng_t, workers))
        with jax.named_scope(scopes.TREE):
            v_sum = values.sum()
            # aggregate backup: n += workers, w += sum(values) along the
            # path; drain whichever in-flight plane Select/Expand incremented
            paths = exp["path"]
            mask = paths >= 0
            idx = jnp.maximum(paths, 0)
            infl = S.infl_plane(tree, sp).at[idx].add(
                -mask.astype(jnp.int32))
            tree = tree.replace(
                visits=tree.visits.at[idx].add(mask * workers),
                value=tree.value.at[idx].add(jnp.where(mask, v_sum, 0.0)),
                **{("unobs" if sp.wu else "vloss"): infl})
        return tree, sel["dup"]

    tree, dups = jax.lax.scan(it, tree, jax.random.split(rng, iters))
    stats = make_stats(iters * workers, iters * workers, dups.sum(), iters)
    return result_from_tree(tree, stats)


@register_strategy("tree")
def tree_parallel(domain, cfg: SearchConfig, rng) -> SearchResult:
    """Tree parallelization with virtual loss (Chaslot et al.): per round,
    ``lanes`` trajectories selected/expanded/played/backed-up together.
    Staleness grows with lanes — the regime the pipeline bounds."""
    sp, threads = cfg.params, max(cfg.lanes, 1)
    rounds = _ceil_div(cfg.budget, threads)
    tree = init_tree(domain, cfg.max_nodes or rounds * threads + 2)

    fused = sp.resolved_wave_select == "mega"

    def _dup_st(sels):
        return {"dup": sels["dup"].sum(),
                "dup_within": sels["dup_within"].sum(),
                "dup_cross": sels["dup_cross"].sum()}

    def round_fn(tree, rng_t):
        if fused:        # whole round through kernels/search_wave (§14)
            tree, sels = S.mega_round(tree, domain, sp, threads,
                                      jnp.asarray(True), rng_t)
            return tree, _dup_st(sels)
        tree, sels = S.select_wave(tree, sp, threads, jnp.asarray(True))
        tree, exps = S.expand_wave(tree, domain, sp, sels)
        po = S.playout_wave(domain, sp, exps, rng_t)
        tree = S.backup_wave(tree, po, sp)
        return tree, _dup_st(sels)

    tree, st = jax.lax.scan(round_fn, tree, jax.random.split(rng, rounds))
    stats = make_stats(rounds * threads, rounds * threads,
                       st["dup"].sum(), rounds)
    extras = {"dup_within": st["dup_within"].sum(),
              "dup_cross": st["dup_cross"].sum()}
    return result_from_tree(tree, stats, extras)


@register_strategy("pipeline")
def pipeline(domain, cfg: SearchConfig, rng) -> SearchResult:
    """The paper's contribution: software-pipelined MCTS.  One scan tick
    co-schedules  B(wave t-3) | P(wave t-2) | E(wave t-1) | S(wave t),  so
    K = 4 waves are in flight; ``lanes`` parallel playout stages per wave
    (lanes == 1 reproduces the linear pipeline of Fig. 3, lanes > 1 the
    nonlinear pipeline of Fig. 5/6).  See DESIGN.md §2."""
    sp, lanes = cfg.params, max(cfg.lanes, 1)
    n_waves = _ceil_div(cfg.budget, lanes)
    nodes = cfg.max_nodes or (n_waves * lanes + 2)
    tree = init_tree(domain, nodes)
    n_ticks = n_waves + (PIPE_STAGES - 1)       # fill + drain

    init_carry = (
        tree,
        S.empty_selection(sp, lanes),                       # S -> E buffer
        S.empty_expansion(sp, lanes, domain),               # E -> P buffer
        S.empty_playout(sp, lanes, domain.num_actions),     # P -> B buffer
    )

    fused = sp.resolved_wave_select == "mega"

    def tick(carry, inp):
        t, rng_t = inp
        tree, buf_se, buf_ep, buf_pb = carry
        wave_valid = t < n_waves                # Select masked during drain
        if fused:     # one B→E→S launch per tick (kernels/search_wave, §14)
            tree, new_se, new_ep, new_pb = S.mega_tick(
                tree, domain, sp, lanes, wave_valid,
                buf_se, buf_ep, buf_pb, rng_t)
        else:
            # Backup stage — wave t-3 (oldest in flight)
            tree = S.backup_wave(tree, buf_pb, sp)
            # Playout stage — wave t-2 (parallel lanes)
            new_pb = S.playout_wave(domain, sp, buf_ep, rng_t)
            # Expand stage — wave t-1
            tree, new_ep = S.expand_wave(tree, domain, sp, buf_se)
            # Select stage — wave t
            tree, new_se = S.select_wave(tree, sp, lanes, wave_valid)
        st = {
            "dup": new_se["dup"].sum(),
            "dup_within": new_se["dup_within"].sum(),
            "dup_cross": new_se["dup_cross"].sum(),
            "completed": buf_pb["valid"].sum(),
            "occupancy": (new_se["valid"].any().astype(jnp.int32)
                          + buf_se["valid"].any().astype(jnp.int32)
                          + buf_ep["valid"].any().astype(jnp.int32)
                          + buf_pb["valid"].any().astype(jnp.int32)),
        }
        return (tree, new_se, new_ep, new_pb), st

    with jax.named_scope(scopes.TREE):
        rngs = jax.random.split(rng, n_ticks)
        ts = jnp.arange(n_ticks)
    (tree, *_), st = jax.lax.scan(tick, init_carry, (ts, rngs))
    with jax.named_scope(scopes.TREE):
        stats = make_stats(n_waves * lanes, st["completed"].sum(),
                           st["dup"].sum(), n_ticks)
        extras = {
            "mean_occupancy": st["occupancy"].mean() / PIPE_STAGES,
            "dup_per_tick": st["dup"],
            "dup_within": st["dup_within"].sum(),
            "dup_cross": st["dup_cross"].sum(),
        }
    return result_from_tree(tree, stats, extras)
