"""Compile guards: the main path's Pallas kernels compile for a TPU v5e.

Interpret mode accepts what the chip's compiler refuses (a value used as
an address, a reshape across the (8, 128) tiles, a bool loop carry), so
these tests compile each kernel at real widths and lane counts for a
*described* v5e chip — nothing runs, and no chip is needed.  Every compile
passes ``kernels="pallas"`` (or calls the kernel) explicitly: the platform
here is the CPU, which would resolve "auto" to the jnp references.

The topology is described inside a module fixture, never while a module is
imported: only the test worker that runs this file loads the TPU library.
It skips when the topology cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.domains.pgame import PGameDomain
from repro.search import SearchConfig, SearchParams, search

DOM = PGameDomain(num_actions=4, game_depth=8, binary_reward=False, seed=3)
LANES = 8
SMOLLM = get_config("smollm-135m")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_ops(compiled, name):
    return sum(name in ln and "tpu_custom_call" in ln
               for ln in compiled.as_text().splitlines())


@pytest.mark.parametrize("method,level_assign,vl_mode,puct,roots,lanes", [
    ("tree", "independent", "loss", False, 0, LANES),
    ("tree", "running", "loss", False, 0, LANES),
    ("pipeline", "independent", "loss", False, 0, LANES),
    ("pipeline", "running", "loss", False, 0, LANES),
    ("pipeline", "independent", "wu", True, 0, LANES),
    # vmapped over roots, as search_batch and the serving engine run it
    ("tree", "independent", "loss", False, 4, LANES),
    ("pipeline", "running", "wu", True, 4, LANES),
    # one lane: a lane's [1, 1] values keep a layout wider waves reduce away
    ("tree", "independent", "loss", False, 0, 1),
    ("pipeline", "running", "wu", True, 0, 1),
])
def test_search_wave_megakernel_compiles(one_chip, method, level_assign,
                                         vl_mode, puct, roots, lanes):
    cfg = SearchConfig(method=method, budget=64, lanes=lanes,
                       kernels="pallas", wave_select="mega",
                       level_assign=level_assign, vl_mode=vl_mode,
                       params=SearchParams(cp=0.7, max_depth=8, puct=puct))
    assert cfg.params.resolved_wave_select == "mega"
    one = lambda k: search(DOM, cfg, jax.random.wrap_key_data(k)) \
        .action_visits
    fn = jax.jit(jax.vmap(one) if roots else one)
    key = _sds((roots, 2) if roots else (2,), jnp.uint32, one_chip)
    compiled = fn.lower(key).compile()
    assert _kernel_ops(compiled, "search_wave") > 0


@pytest.mark.parametrize("running", (False, True))
def test_uct_select_compiles(one_chip, running):
    from repro.kernels.uct_select import ops as uops
    a = 4
    f32 = lambda: _sds((LANES, a), jnp.float32, one_chip)
    pn = _sds((LANES,), jnp.float32, one_chip)
    valid = _sds((LANES, a), jnp.bool_, one_chip)
    if running:
        pid = _sds((LANES,), jnp.int32, one_chip)
        fn = jax.jit(lambda n, w, vl, p, v, i: uops.uct_argmax_running(
            n, w, vl, p, i, valid=v, cp=1.0))
        compiled = fn.lower(f32(), f32(), f32(), pn, valid, pid).compile()
    else:
        fn = jax.jit(lambda n, w, vl, p, v: uops.uct_argmax(
            n, w, vl, p, valid=v, cp=1.0))
        compiled = fn.lower(f32(), f32(), f32(), pn, valid).compile()
    assert _kernel_ops(compiled, "uct_select") > 0


@pytest.mark.parametrize("sk", (268, 1024))
def test_decode_attention_compiles(one_chip, sk):
    """smollm-135m's widths at the serving cache rows: 256-token buffer +
    search depth 8 + rollout 4, and a longer row."""
    from repro.kernels.decode_attention import ops as da
    h, hkv, d, b = SMOLLM.n_heads, SMOLLM.kv_heads, SMOLLM.head_dim, 16
    dt = SMOLLM.jdtype
    fn = jax.jit(lambda q, k, v, n: da.decode_attention(q, k, v, n))
    compiled = fn.lower(_sds((b, 1, h, d), dt, one_chip),
                        _sds((b, sk, hkv, d), dt, one_chip),
                        _sds((b, sk, hkv, d), dt, one_chip),
                        _sds((b,), jnp.int32, one_chip)).compile()
    assert _kernel_ops(compiled, "decode_attention") > 0


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import ops as fa
    h, hkv, d, s = SMOLLM.n_heads, SMOLLM.kv_heads, SMOLLM.head_dim, 268
    dt = SMOLLM.jdtype
    fn = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    compiled = fn.lower(_sds((4, s, h, d), dt, one_chip),
                        _sds((4, s, hkv, d), dt, one_chip),
                        _sds((4, s, hkv, d), dt, one_chip)).compile()
    assert _kernel_ops(compiled, "flash_attention") > 0


def test_sharded_search_compiles(topo):
    """Over the 2x2 host's four chips each device runs its own roots in a
    shard_map: the compiler cannot partition a Mosaic kernel itself."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.search.api import _batch_domains
    from repro.search.sharding import per_device
    mesh = Mesh(np.asarray(topo.devices), ("batch",))
    shard = NamedSharding(mesh, PartitionSpec("batch"))
    doms = [PGameDomain(num_actions=4, game_depth=8, seed=3,
                        threshold=0.3 + 0.05 * i) for i in range(8)]
    cfg = SearchConfig(method="pipeline", budget=64, lanes=LANES,
                       kernels="pallas", params=SearchParams(max_depth=8))
    make, batched = _batch_domains(doms)
    fn = jax.jit(per_device(jax.vmap(
        lambda bat, k: search(make(bat), cfg, jax.random.wrap_key_data(k))
        .action_visits), mesh, 2))
    bat = jax.tree_util.tree_map(
        lambda x: _sds(jnp.shape(x), jnp.asarray(x).dtype, shard), batched)
    compiled = fn.lower(bat, _sds((8, 2), jnp.uint32, shard)).compile()
    assert _kernel_ops(compiled, "search_wave") > 0


@pytest.mark.parametrize("stateful", (False, True))
def test_serving_step_compiles(one_chip, stateful):
    """The engine's per-token program, cold (``BatchedSearcher``) and
    stateful (``ReusableSearcher``: KV splice + tree reuse), at smollm-135m's
    attention widths with two layers and a cut vocabulary: it must hold the
    megakernel and ``decode_attention``, not fall back to the references."""
    from repro.models.base import get_family
    from repro.serving.mcts_decode import (MCTSDecodeConfig,
                                           make_batched_searcher)
    cfg = SMOLLM.replace(n_layers=2, vocab_size=512, use_pallas=True)
    dcfg = MCTSDecodeConfig(budget=8, lanes=2, search_depth=2, rollout_len=1,
                            kernels="pallas", kv_splice=stateful,
                            tree_reuse=stateful)
    params = get_family(cfg).init(cfg, jax.random.key(0))
    batch, buf_len = 2, 64
    s = make_batched_searcher(cfg, params, dcfg, batch=batch)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    args = [on_chip(params), _sds((batch, buf_len), jnp.int32, one_chip),
            _sds((batch,), jnp.int32, one_chip),
            on_chip(jax.eval_shape(lambda: jax.random.split(
                jax.random.key(0), batch)))]
    if stateful:
        args.append(on_chip(jax.eval_shape(lambda: s.init_carry(buf_len))))
    compiled = s._jstep.lower(*args).compile()
    assert _kernel_ops(compiled, "search_wave") > 0
    assert _kernel_ops(compiled, "decode_attention") > 0
