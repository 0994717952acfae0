"""Multi-device scaling of batched multi-root search (DESIGN.md §9):
total and per-device playouts/s of the batch axis sharded over a 1-D mesh
vs the single-device vmap baseline, at B = 4 roots per device.

It needs several visible devices and raises on one: run it on a
multi-chip host, or on fake CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
(what CI does) — rows then time the CPU, not a chip.
"""
from __future__ import annotations

import time

import jax

from repro.core.domains.pgame import PGameDomain
from repro.search import SearchConfig, SearchParams, search

DOM = PGameDomain(num_actions=4, game_depth=8, binary_reward=False, seed=1)
SP = SearchParams(cp=0.7, max_depth=8)


def _time(f, *args, reps=3):
    f(*args)                                   # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps


def _measure(report, smoke: bool):
    from repro.launch.mesh import make_search_mesh
    from repro.parallel.compat import batch_sharding

    ndev = jax.device_count()
    budget = 32 if smoke else 256
    per_dev = 1 if smoke else 4
    b = per_dev * ndev
    cfg = SearchConfig(method="pipeline", budget=budget, lanes=8, params=SP,
                       keep_tree=False)
    rngs = jax.random.split(jax.random.key(0), b)
    body = jax.vmap(lambda r: search(DOM, cfg, r).action_visits)

    # baseline: the whole batch vmapped on one device (uncommitted inputs)
    t_base = _time(jax.jit(body), rngs, reps=1 if smoke else 3)
    report(f"vmap_1dev_B{b}", t_base * 1e6,
           f"total_playouts_per_s={b * budget / t_base:,.0f}")

    sharded = batch_sharding(make_search_mesh())
    rngs_s = jax.device_put(rngs, sharded)
    t_shard = _time(jax.jit(body, out_shardings=sharded), rngs_s,
                    reps=1 if smoke else 3)
    report(f"sharded_{ndev}dev_B{b}", t_shard * 1e6,
           f"total_playouts_per_s={b * budget / t_shard:,.0f} "
           f"per_dev={b * budget / t_shard / ndev:,.0f} "
           f"speedup_vs_1dev={t_base / t_shard:.2f}x")

    # the shipped API end-to-end (shard_search_batch: trace + device_put +
    # pad/unpad every call) — tracks regressions the steady-state rows above
    # can't see
    from repro.search import shard_search_batch
    doms = [DOM] * b
    key = jax.random.key(0)
    jax.block_until_ready(
        shard_search_batch(doms, cfg, key).action_visits)     # warm libraries
    t0 = time.perf_counter()
    jax.block_until_ready(
        shard_search_batch(doms, cfg, key).action_visits)
    t_api = time.perf_counter() - t0
    report(f"shard_search_batch_api_B{b}", t_api * 1e6,
           f"total_playouts_per_s={b * budget / t_api:,.0f} "
           f"(includes per-call retrace)")


def run(report, smoke: bool = False):
    if jax.device_count() < 2:
        raise RuntimeError(
            f"shard_scaling needs several devices, found "
            f"{jax.device_count()} ({jax.devices()[0].platform}); run it on "
            f"a multi-chip host or with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    _measure(report, smoke)
