"""The unified public search API (DESIGN.md §3–§5).

One entry point for every parallelization pattern in the paper:

    from repro.search import SearchConfig, search, search_batch

    res = search(domain, SearchConfig(method="pipeline", budget=256,
                                      lanes=8), jax.random.key(0))
    res.best_action          # recommended root action (robust child)
    res.action_visits        # [A] root child visit counts
    res.stats                # common schema, identical keys for all methods

Strategies are looked up in a string-keyed registry so new parallelizations
plug in without touching callers:

    @register_strategy("my_method")
    def _my_method(domain, cfg, rng) -> SearchResult: ...

``search_batch`` vmaps B independent searches into ONE device program
(batched multi-root search) — the scaling primitive that lets serving run a
whole batch of decode requests per device call.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stages import SearchParams
from repro.core.tree import Tree, root_child_stats
from repro.search.domain import Domain, missing_members

__all__ = [
    "STATS_KEYS", "SearchConfig", "SearchResult", "StrategyFn",
    "register_strategy", "get_strategy", "list_strategies",
    "make_stats", "result_from_tree", "search", "search_batch",
]

# Every strategy returns exactly this stats key set (ISSUE: "identical
# across all five").  ``playouts`` is the headline number and always equals
# ``playouts_completed``; ``playouts_requested`` is the nominal budget after
# lane/worker rounding (the two differ only transiently, e.g. a capped tree).
STATS_KEYS = ("playouts", "playouts_requested", "playouts_completed",
              "duplicates", "ticks")

StrategyFn = Callable[..., "SearchResult"]

_STRATEGIES: Dict[str, StrategyFn] = {}


class SearchResult(NamedTuple):
    """Standardized result pytree — identical field set for every strategy.

    ``tree`` is the full search tree for single-tree strategies, ``None`` for
    root parallelization (workers' trees are merged into the root stats) or
    when ``SearchConfig.keep_tree`` is False.  ``stats`` always carries
    exactly ``STATS_KEYS`` (int32 scalars); ``extras`` holds per-strategy
    diagnostics (e.g. the pipeline's ``mean_occupancy``) and may differ
    between strategies.
    """

    action_visits: jnp.ndarray          # [A] i32 root child visit counts
    action_value: jnp.ndarray           # [A] f32 root child reward sums
    best_action: jnp.ndarray            # scalar i32 (robust child)
    tree: Optional[Tree]                # full tree, or None
    stats: Dict[str, jnp.ndarray]       # common schema: STATS_KEYS
    extras: Dict[str, Any]              # strategy-specific diagnostics


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """One config for all strategies.

    method:    registry key — "sequential" | "root" | "leaf" | "tree"
               | "pipeline" (see ``list_strategies()``).
    budget:    total playouts.  Strategies with ``lanes`` > 1 round up to a
               whole number of waves/rounds; ``stats["playouts_requested"]``
               records the rounded value.
    lanes:     degree of parallelism.  Unifies the old per-runner names:
               pipeline lanes == tree-parallel threads == root/leaf workers.
               Ignored by "sequential".
    max_nodes: tree capacity (0 -> strategy default, sized to the budget).
    keep_tree: when False, ``SearchResult.tree`` is dropped (saves memory in
               ``search_batch`` fan-outs).
    params:    the shared UCT/virtual-loss knobs (core.stages.SearchParams).
    kernels /
    wave_select /
    vl_mode /
    level_assign: top-level conveniences for the consolidated kernel pair,
               the in-flight-statistics mode, and the within-level lane
               assignment (DESIGN.md §14/§15/§16).  Anything other than the
               default is forwarded into ``params`` at construction, so
               ``SearchConfig(kernels="pallas")`` ==
               ``SearchConfig(params=SearchParams(kernels="pallas"))``.
               ``vl_mode``: "loss" (virtual loss, the unchanged default) or
               "wu" (WU-UCT unobserved counts — Q from completed stats only).
               ``level_assign``: "independent" (default) or "running" (the
               within-level running-assignment scan — co-located lockstep
               lanes spread instead of stacking).
    """

    method: str = "sequential"
    budget: int = 256
    lanes: int = 1
    max_nodes: int = 0
    keep_tree: bool = True
    params: SearchParams = dataclasses.field(default_factory=SearchParams)
    kernels: str = "auto"
    wave_select: str = "auto"
    vl_mode: str = "loss"
    level_assign: str = "independent"

    def __post_init__(self):
        upd = {}
        if self.kernels != "auto" and self.params.kernels == "auto":
            upd["kernels"] = self.kernels
        if self.wave_select != "auto" and self.params.wave_select == "auto":
            upd["wave_select"] = self.wave_select
        if self.vl_mode != "loss" and self.params.vl_mode == "loss":
            upd["vl_mode"] = self.vl_mode
        if self.level_assign != "independent" \
                and self.params.level_assign == "independent":
            upd["level_assign"] = self.level_assign
        if upd:
            object.__setattr__(
                self, "params", dataclasses.replace(self.params, **upd))


# ---------------------------------------------------------------------------
# strategy registry
# ---------------------------------------------------------------------------
def register_strategy(name: str) -> Callable[[StrategyFn], StrategyFn]:
    """Decorator: register ``fn(domain, cfg, rng) -> SearchResult`` under
    ``name``.  Re-registering a name overwrites it (supports reloads)."""
    def deco(fn: StrategyFn) -> StrategyFn:
        _STRATEGIES[name] = fn
        return fn
    return deco


def get_strategy(name: str) -> StrategyFn:
    _ensure_builtin_strategies()
    try:
        return _STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown search method {name!r}; "
            f"registered: {list_strategies()}") from None


def list_strategies() -> List[str]:
    _ensure_builtin_strategies()
    return sorted(_STRATEGIES)


def _ensure_builtin_strategies() -> None:
    # Imported lazily: strategies.py imports this module for the decorator.
    from repro.search import strategies  # noqa: F401


# ---------------------------------------------------------------------------
# result assembly helper (used by strategies.py)
# ---------------------------------------------------------------------------
def make_stats(requested, completed, duplicates, ticks) -> Dict[str, jnp.ndarray]:
    completed = jnp.asarray(completed, jnp.int32)
    return {
        "playouts": completed,
        "playouts_requested": jnp.asarray(requested, jnp.int32),
        "playouts_completed": completed,
        "duplicates": jnp.asarray(duplicates, jnp.int32),
        "ticks": jnp.asarray(ticks, jnp.int32),
    }


def result_from_tree(tree: Tree, stats: Dict[str, jnp.ndarray],
                     extras: Optional[Dict[str, Any]] = None) -> SearchResult:
    n, w, valid = root_child_stats(tree)
    best = jnp.argmax(jnp.where(valid, n, -1)).astype(jnp.int32)
    return SearchResult(action_visits=n.astype(jnp.int32), action_value=w,
                        best_action=best, tree=tree, stats=stats,
                        extras=extras or {})


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def search(domain, cfg: SearchConfig, rng) -> SearchResult:
    """Run one search.  Pure and jit/vmap-compatible: strategies are built
    from lax control flow, so ``jax.jit(lambda r: search(dom, cfg, r))``
    compiles to a single device program."""
    if not isinstance(domain, Domain):
        raise TypeError(
            f"{type(domain).__name__} does not satisfy the Domain protocol "
            f"(missing {missing_members(domain)}); see repro.search.domain")
    res = get_strategy(cfg.method)(domain, cfg, rng)
    missing = set(STATS_KEYS) ^ set(res.stats)
    if missing:
        raise RuntimeError(
            f"strategy {cfg.method!r} broke the common stats schema "
            f"(symmetric difference: {sorted(missing)})")
    if not cfg.keep_tree:
        res = res._replace(tree=None)
    return res


def search_batch(domains: Sequence[Any], cfg: SearchConfig, rng,
                 *, mesh=None) -> SearchResult:
    """Batched multi-root search: B independent searches in ONE XLA program.

    ``domains`` is a sequence of B domain instances of the same type.  Fields
    that differ between instances (e.g. each request's prompt) must be
    array-valued; they are stacked and vmapped over.  Fields that are shared
    (model params, static config) stay closed over once.

    RNG contract: ``rng`` is split into B keys, so
    ``search_batch(domains, cfg, rng).action_visits[i]`` equals
    ``search(domains[i], cfg, jax.random.split(rng, B)[i]).action_visits``.

    Multi-device: pass ``mesh`` (a 1-D device mesh) to shard the batch axis
    across devices, or rely on auto-sharding — when more than one device is
    visible and the call is not inside a trace, the batch is sharded over a
    default all-device mesh.  Per-root results are identical either way
    (DESIGN.md §9); pass ``mesh=False`` to force the single-device vmap.

    Returns a ``SearchResult`` whose every leaf gains a leading batch axis.
    """
    domains = list(domains)
    if not domains:
        raise ValueError("search_batch needs at least one domain")
    # auto-shard only when there is real batch parallelism to split: at B=1
    # padding to the mesh would run device_count searches to keep one
    if mesh is None and len(domains) > 1 and jax.device_count() > 1 \
            and not _contains_tracer(rng, *domains):
        from repro.launch.mesh import make_search_mesh
        mesh = make_search_mesh()
    if mesh is not None and mesh is not False:
        from repro.search.sharding import shard_search_batch
        return shard_search_batch(domains, cfg, rng, mesh=mesh)
    return search_keys(domains, cfg, jax.random.split(rng, len(domains)))


def search_keys(domains: Sequence[Any], cfg: SearchConfig, keys
                ) -> SearchResult:
    """``search_batch`` on one device with the per-root keys already split:
    row i is ``search(domains[i], cfg, keys[i])``.  One vmap and no device
    placement, so it also serves as the per-device body of a ``shard_map``
    (the serving searchers' mesh path)."""
    domains = list(domains)
    make, batched = _batch_domains(domains)
    if batched is None:
        return jax.vmap(lambda r: search(domains[0], cfg, r))(keys)
    return jax.vmap(lambda bat, r: search(make(bat), cfg, r))(batched, keys)


def _contains_tracer(*objs) -> bool:
    """True when any value (or dataclass field / pytree leaf thereof) is a
    jax tracer — i.e. the caller is already inside jit/vmap, where device
    placement is owned by the enclosing program, not by auto-sharding."""
    for o in objs:
        vals = ([getattr(o, f.name) for f in dataclasses.fields(o)]
                if dataclasses.is_dataclass(o) and not isinstance(o, type)
                else [o])
        for v in vals:
            if any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(v)):
                return True
    return False


def _static_eq(a, b) -> bool:
    """True when two field values are interchangeable as static config."""
    if a is b:
        return True
    if isinstance(a, (int, float, str, bool, bytes, type(None))):
        return type(a) is type(b) and a == b
    if dataclasses.is_dataclass(a) and type(a) is type(b):
        try:
            return bool(a == b)       # equal-valued configs built separately
        except Exception:  # noqa: BLE001 — array fields make == ambiguous
            return False
    # pytrees of concrete arrays (e.g. the same model params built twice):
    # equal values are shared static config — without this, search_batch
    # would silently stack B copies of the weights
    try:
        if (jax.tree_util.tree_structure(a)
                != jax.tree_util.tree_structure(b)):
            return False
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        if any(isinstance(x, jax.core.Tracer) for x in la + lb):
            return False              # traced values genuinely vary
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(la, lb))
    except Exception:  # noqa: BLE001 — non-array leaves etc.
        return False


def _batch_domains(domains):
    """Split a list of same-typed domains into (rebuild_fn, stacked_fields).

    Returns (None, None) when every instance is identical — the caller then
    vmaps over rng only.  Otherwise each differing dataclass field is stacked
    leaf-wise into a leading batch axis and ``rebuild_fn`` reconstructs one
    domain from one batch slice via ``dataclasses.replace``.
    """
    d0 = domains[0]
    if all(d is d0 for d in domains[1:]):
        return None, None
    if any(type(d) is not type(d0) for d in domains[1:]):
        raise TypeError("search_batch domains must all share one type; got "
                        f"{sorted({type(d).__name__ for d in domains})}")
    if not dataclasses.is_dataclass(d0):
        raise TypeError(
            f"search_batch over distinct {type(d0).__name__} instances "
            "requires a dataclass domain (so differing fields can be "
            "stacked); pass identical instances or make it a dataclass")
    varying = {}
    for f in dataclasses.fields(d0):
        vals = [getattr(d, f.name) for d in domains]
        if all(_static_eq(v, vals[0]) for v in vals[1:]):
            continue
        if any(v is None or isinstance(v, (int, str, bytes)) for v in vals):
            # ints are shape-determining (num_actions, depths, seeds) — a
            # tracer there crashes deep inside the strategy; fail clearly
            raise TypeError(
                f"search_batch domains disagree on field {f.name!r} "
                f"({[getattr(d, f.name) for d in domains]!r}); static "
                "Python fields must be equal across the batch — only "
                "array-valued (or float) fields may vary")
        try:
            varying[f.name] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *vals)
        except Exception as e:  # noqa: BLE001 — re-raise with field context
            raise TypeError(
                f"search_batch cannot batch field {f.name!r} of "
                f"{type(d0).__name__}: values differ but are not stackable "
                f"arrays ({e})") from e
    if not varying:
        return None, None

    def make(bat):
        return dataclasses.replace(d0, **bat)

    return make, varying
