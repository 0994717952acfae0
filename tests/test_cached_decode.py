"""Cached-vs-uncached MCTS decode parity (DESIGN.md §10).

``CachedLMDecodeDomain`` must make the same decisions as the uncached
``LMDecodeDomain`` — token for token through the serving path, and
visit-for-visit at the search level — across every registered strategy,
for equal and ragged prompt lengths, on the plain and the mesh-sharded
paths.  The cached domain amortizes compute only; any behavioural drift is
a bug in the cache threading.
"""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")

from repro.core.domains.lm_decode import (CachedLMDecodeDomain,  # noqa: E402
                                          LMDecodeDomain)
from repro.models.base import (ModelConfig, get_family,  # noqa: E402
                               seq_prefill, seq_step)
from repro.search import (SearchConfig, SearchParams, check_domain,  # noqa: E402
                          search)
from repro.serving import (EngineConfig, MCTSDecodeConfig, Request,  # noqa: E402
                           ServingEngine, mcts_decode_batch)

CFG = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32", ce_chunk=8, remat=False)
METHODS = ("sequential", "root", "leaf", "tree", "pipeline")
EQUAL = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
RAGGED = ([1, 2, 3, 4, 5], [7, 8])

multi = jax.device_count() >= 2
needs_mesh = pytest.mark.skipif(
    not multi, reason="needs >1 device (run in the CI multi-device job; the "
    "subprocess test below covers single-device sessions)")


@pytest.fixture(scope="module")
def params():
    return get_family(CFG).init(CFG, jax.random.key(0))


def _dcfg(method, cached):
    return MCTSDecodeConfig(method=method, num_actions=3, budget=6, lanes=2,
                            search_depth=2, rollout_len=2, cached=cached)


def test_cached_domain_satisfies_contract(params):
    dom = CachedLMDecodeDomain(cfg=CFG, params=params,
                               prompt=jnp.asarray([1, 2, 3], jnp.int32),
                               num_actions=3, search_depth=2, rollout_len=2)
    assert check_domain(dom)


@pytest.mark.parametrize("method", METHODS)
def test_search_level_parity(params, method):
    """Same visits, values, and recommended action for one search."""
    kw = dict(cfg=CFG, params=params,
              prompt=jnp.asarray([1, 2, 3, 4], jnp.int32),
              num_actions=3, search_depth=2, rollout_len=2)
    scfg = SearchConfig(method=method, budget=6, lanes=2, keep_tree=False,
                        params=SearchParams(cp=1.0, max_depth=2, puct=True))
    ru = search(LMDecodeDomain(**kw), scfg, jax.random.key(3))
    rc = search(CachedLMDecodeDomain(**kw), scfg, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(rc.action_visits),
                                  np.asarray(ru.action_visits))
    np.testing.assert_allclose(np.asarray(rc.action_value),
                               np.asarray(ru.action_value), atol=1e-5)
    assert int(rc.best_action) == int(ru.best_action)


@pytest.mark.parametrize("prompts", [EQUAL, RAGGED], ids=["equal", "ragged"])
@pytest.mark.parametrize("method", METHODS)
def test_decode_parity_token_for_token(params, method, prompts):
    """The serving path emits identical token streams cached and uncached,
    for equal-length and ragged prompt batches."""
    out_c = mcts_decode_batch(CFG, params, prompts, 2, _dcfg(method, True))
    out_u = mcts_decode_batch(CFG, params, prompts, 2, _dcfg(method, False))
    assert out_c == out_u


def test_generic_fallback_matches_family_step(params, monkeypatch):
    """With the dense family's prefill_fn/step_fn removed, the pure-JAX
    fallback (full forward from a token-buffer cache) produces the same
    logits — families without an incremental path stay correct."""
    from repro.models import transformer
    toks = jnp.zeros((10,), jnp.int32).at[:4].set(jnp.asarray([1, 2, 3, 4]))
    plen = jnp.int32(4)
    lg_f, cache_f = seq_prefill(CFG, params, toks, plen)
    monkeypatch.delattr(transformer, "prefill_fn")
    monkeypatch.delattr(transformer, "step_fn")
    lg_g, cache_g = seq_prefill(CFG, params, toks, plen)
    np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_f), atol=1e-5)
    lg_g2, _ = seq_step(CFG, params, cache_g, jnp.int32(9), plen)
    monkeypatch.undo()
    lg_f2, _ = seq_step(CFG, params, cache_f, jnp.int32(9), plen)
    np.testing.assert_allclose(np.asarray(lg_g2), np.asarray(lg_f2), atol=1e-5)


def test_engine_slot_reuse_no_leak(params):
    """A request decoded after another request occupied (and reset) its slot
    emits the same tokens as when decoded alone.  Decisions of the LM domain
    are rng-independent (greedy rollouts), so any difference is state
    leaking across requests through the slot."""
    dcfg = _dcfg("pipeline", True)

    def run(prompts):
        eng = ServingEngine(CFG, params, EngineConfig(
            max_batch=1, max_seq=16, decode="mcts", mcts=dcfg))
        reqs = [Request(uid=i, prompt=np.asarray(p, np.int32),
                        max_new_tokens=2) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs]

    alone = run([[9, 8, 7]])
    after_other = run([[1, 2, 3, 4, 5], [9, 8, 7]])
    assert after_other[1] == alone[0]


@needs_mesh
@pytest.mark.parametrize("prompts", ["equal", "ragged"])
def test_mesh_parity_cached_vs_uncached(params, prompts):
    """Cached == uncached on the auto-sharded multi-device path too, and the
    meshed cached stream matches the forced single-device vmap stream when B
    divides the mesh (same rng splits, DESIGN.md §9)."""
    b = jax.device_count()
    if prompts == "equal":
        batch = (np.arange(b * 3).reshape(b, 3) % 60 + 1).astype(np.int32)
    else:
        batch = [list(range(1, 2 + i % 3)) for i in range(b)]
    out_c = mcts_decode_batch(CFG, params, batch, 2, _dcfg("pipeline", True))
    out_u = mcts_decode_batch(CFG, params, batch, 2, _dcfg("pipeline", False))
    assert out_c == out_u
    out_v = mcts_decode_batch(CFG, params, batch, 2, _dcfg("pipeline", True),
                              mesh=False)
    assert out_c == out_v


def test_cached_parity_subprocess_8dev():
    """Single-device sessions: the mesh-sharded cached-vs-uncached parity on
    8 forced host devices (the pattern of tests/test_sharding.py)."""
    code = textwrap.dedent("""
        import dataclasses
        import jax, numpy as np
        jax.config.update("jax_default_matmul_precision", "highest")
        from repro.models.base import ModelConfig, get_family
        from repro.serving import MCTSDecodeConfig, mcts_decode_batch
        assert jax.device_count() == 8
        CFG = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32", ce_chunk=8, remat=False)
        params = get_family(CFG).init(CFG, jax.random.key(0))
        dcfg = MCTSDecodeConfig(method="pipeline", num_actions=3, budget=6,
                                lanes=2, search_depth=2, rollout_len=2)
        # divisible B: meshed cached == meshed uncached == unmeshed cached
        eq = (np.arange(24).reshape(8, 3) % 60 + 1).astype(np.int32)
        c = mcts_decode_batch(CFG, params, eq, 1, dcfg)
        u = mcts_decode_batch(CFG, params, eq, 1,
                              dataclasses.replace(dcfg, cached=False))
        v = mcts_decode_batch(CFG, params, eq, 1, dcfg, mesh=False)
        assert c == u == v, (c, u, v)
        # ragged non-divisible B: pads to the mesh, parity still holds
        rg = [[1, 2, 3, 4], [5, 6], [7, 8, 9]]
        c = mcts_decode_batch(CFG, params, rg, 1, dcfg)
        u = mcts_decode_batch(CFG, params, rg, 1,
                              dataclasses.replace(dcfg, cached=False))
        assert c == u, (c, u)
        # the root counters leave the meshed program beside the tokens,
        # sharded like them, equal to the unmeshed program's
        from repro.serving import make_batched_searcher
        from repro.serving.mcts_decode import unpack
        lens = np.full((8,), 3, np.int32)
        key = jax.random.key(3)
        outs = [make_batched_searcher(CFG, params, dcfg, batch=8,
                                      mesh=m).search(eq, lens, key)
                for m in (None, False)]
        assert len(outs[0].sharding.device_set) == 8
        (tm, cm), (t1, c1) = [unpack(o) for o in jax.device_get(outs)]
        assert (tm == t1).all()
        for k in ("root_visits", "playouts", "duplicates"):
            assert (cm[k] == c1[k]).all(), k
        # one row per device against eight vmapped rows: float rounding
        np.testing.assert_allclose(cm["root_values"], c1["root_values"],
                                   rtol=1e-5)
        assert (cm["playouts"] == dcfg.budget).all()
        assert (cm["root_visits"].sum(-1) <= cm["playouts"]).all()
        st = make_batched_searcher(
            CFG, params, dataclasses.replace(dcfg, kv_splice=True), batch=8)
        carry = st.init_carry(eq.shape[1])
        for i in range(8):
            carry = st.admit(carry, i, eq[i], 3)
        out, _ = st.search(eq, lens, key, carry)
        assert len(out.sharding.device_set) == 8
        assert (unpack(jax.device_get(out))[1]["playouts"]
                == dcfg.budget).all()
        print("OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu",
             "PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/root"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout
