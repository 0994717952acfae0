"""repro.search — the single public API for all MCTS parallelizations.

    from repro.search import SearchConfig, SearchParams, search, search_batch

    res = search(domain, SearchConfig(method="pipeline", budget=256,
                                      lanes=8), jax.random.key(0))

Entry points
    search(domain, cfg, rng)            one search, jit/vmap-compatible
    search_batch(domains, cfg, rng)     B searches in ONE device program
                                        (auto-shards over a device mesh)
    search_keys(domains, cfg, keys)     the same on one device with the
                                        per-root keys given
    shard_search_batch(...)             the explicit mesh-sharded form
                                        (single- or multi-host meshes)
    ft_search_batch(...)                the elastic fault-tolerant driver
                                        (requeue-and-shrink; DESIGN §13)

Configuration
    SearchConfig    method/budget/lanes/max_nodes/keep_tree + ``params``
    SearchParams    UCT knobs: cp, vl_weight, max_depth, puct, use_pallas,
                    wave_select ("scan" | "lockstep" | "auto" — DESIGN §11)

Extension points
    Domain          structural protocol every strategy accepts
    SupportsPriors  optional PUCT-priors extension; check_domain(d) validates
    register_strategy(name)  add a parallelization; list_strategies() names
                    the built-ins: sequential, root, leaf, tree, pipeline

Results
    SearchResult    action_visits / action_value / best_action / tree /
                    stats (always exactly STATS_KEYS) / extras

See README.md (quickstart), DESIGN.md §3–§5 (API design), §9 (sharding),
§11 (lockstep wave selection).
"""
from repro.core.stages import SearchParams  # noqa: F401  (re-export)
from repro.search.api import (STATS_KEYS, SearchConfig,  # noqa: F401
                              SearchResult, get_strategy, list_strategies,
                              register_strategy, search, search_batch,
                              search_keys)
from repro.search.domain import (Domain, SupportsPriors,  # noqa: F401
                                 check_domain)
from repro.search.sharding import (shard_search_batch,  # noqa: F401
                                   shard_search_keys)
from repro.search.ft import (ElasticSearchDriver, FTReport,  # noqa: F401
                             FTSearchConfig, ft_search_batch)
from repro.search import strategies  # noqa: F401  (registers the built-ins)

__all__ = [
    "STATS_KEYS", "SearchConfig", "SearchParams", "SearchResult",
    "Domain", "SupportsPriors", "check_domain",
    "search", "search_batch", "search_keys", "shard_search_batch",
    "shard_search_keys",
    "ElasticSearchDriver", "FTReport", "FTSearchConfig", "ft_search_batch",
    "get_strategy", "list_strategies", "register_strategy",
    "strategies",
]
