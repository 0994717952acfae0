"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,sq,sk,h,hkv,d,causal", [
    (2, 128, 128, 4, 2, 64, True),
    (1, 64, 64, 3, 3, 32, True),
    (2, 100, 100, 4, 1, 64, True),      # padding path
    (1, 96, 160, 2, 2, 128, False),     # cross-length, non-causal
    (1, 256, 256, 2, 1, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, sq, sk, h, hkv, d, causal, dtype):
    from repro.kernels.flash_attention import ops as fa
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    o_ref = fa.flash_attention(q, k, v, causal=causal, use_ref=True)
    o_ker = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                               blk_q=64, blk_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,sk,h,hkv,d", [
    (2, 256, 4, 2, 64), (3, 1000, 4, 4, 32), (1, 512, 8, 1, 128),
])
def test_decode_attention(b, sk, h, hkv, d):
    from repro.kernels.decode_attention import ops as da
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, sk, hkv, d))
    v = jax.random.normal(ks[2], (b, sk, hkv, d))
    vl = jax.random.randint(ks[3], (b,), 1, sk + 1)
    o_ref = da.decode_attention(q, k, v, vl, use_ref=True)
    o_ker = da.decode_attention(q, k, v, vl, interpret=True, blk_k=128)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# The shapes the cached MCTS decode path (models.transformer.step_fn via
# CachedLMDecodeDomain, DESIGN.md §10) actually issues: non-power-of-two
# cache rows sized prompt+depth+rollout, with per-row valid lengths
# ``pos + 1`` anywhere from a 1-token prefix up to the full row.  The kernel
# builds its compiler params through ``compat.tpu_compiler_params``.
@pytest.mark.parametrize("b,sk,h,hkv,d", [
    (4, 28, 4, 2, 8),       # test-size row: plen 16 + depth 8 + rollout 4
    (2, 44, 4, 2, 16),      # bench smoke row: plen 32 + depth 8 + rollout 4
    (3, 27, 3, 1, 32),      # odd row length, MQA grouping
])
def test_decode_attention_cached_domain_shapes(b, sk, h, hkv, d):
    from repro.kernels.decode_attention import ops as da
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, sk, hkv, d))
    v = jax.random.normal(ks[2], (b, sk, hkv, d))
    # ragged position offsets across the batch, pinning both extremes: the
    # first post-prefill step (valid 1 would mean an empty prefix; the domain
    # never goes below plen+1) and a row filled to capacity (valid == sk)
    base = np.linspace(1, sk, b).astype(np.int32)
    for vl in (jnp.asarray(base),
               jnp.full((b,), 1, jnp.int32),
               jnp.full((b,), sk, jnp.int32)):
        o_ref = da.decode_attention(q, k, v, vl, use_ref=True)
        o_ker = da.decode_attention(q, k, v, vl, interpret=True, blk_k=128)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


def test_decode_attention_matches_step_fn_issuance():
    """End-to-end shape check: the kernel path agrees with the ref oracle on
    the exact (q, cache, valid) stream a cached-domain rollout issues —
    sequential single-token steps with growing position offsets."""
    from repro.kernels.decode_attention import ops as da
    b, sk, h, hkv, d = 2, 20, 2, 1, 16
    ks = jax.random.split(jax.random.key(12), 3)
    k = jax.random.normal(ks[1], (b, sk, hkv, d))
    v = jax.random.normal(ks[2], (b, sk, hkv, d))
    plen = np.array([3, 7], np.int32)
    for step in range(4):                       # rollout_len=4 trajectory
        q = jax.random.normal(jax.random.fold_in(ks[0], step), (b, 1, h, d))
        vl = jnp.asarray(plen + 1 + step)       # valid = pos + 1
        o_ref = da.decode_attention(q, k, v, vl, use_ref=True)
        o_ker = da.decode_attention(q, k, v, vl, interpret=True, blk_k=128)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# wkv6 (rwkv6 recurrence)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,h,n,chunk", [
    (2, 64, 2, 16, 16), (1, 100, 3, 8, 32), (2, 48, 4, 32, 16),
])
def test_wkv6_kernel(b, t, h, n, chunk):
    from repro.kernels.rwkv6_scan import kernel as K, ref as R
    ks = jax.random.split(jax.random.key(2), 5)
    r = jax.random.normal(ks[0], (b, t, h, n)) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, n)) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, n)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.2 + 0.8
    u = jax.random.normal(ks[4], (h, n)) * 0.3
    st = jax.random.normal(jax.random.key(9), (b, h, n, n)) * 0.1
    y1, s1 = R.wkv6_ref(r, k, v, w, u, st)
    y2, s2 = K.wkv6_pallas(r, k, v, w, u, st, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-4, rtol=2e-4)


def test_wkv6_chunked_ops_matches_sequential():
    from repro.kernels.rwkv6_scan import ops, ref
    ks = jax.random.split(jax.random.key(3), 5)
    b, t, h, n = 2, 40, 2, 8
    r = jax.random.normal(ks[0], (b, t, h, n)) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, n)) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, n)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.3 + 0.7
    u = jax.random.normal(ks[4], (h, n)) * 0.3
    st = jnp.zeros((b, h, n, n))
    y1, s1 = ref.wkv6_ref(r, k, v, w, u, st)
    y2, s2 = ops.wkv6_chunked(r, k, v, w, u, st, chunk=16)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# ssd (mamba2 recurrence)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (2, 64, 2, 16, 8, 16), (1, 96, 4, 8, 16, 32), (2, 80, 2, 32, 64, 16),
])
def test_ssd_kernel(b, t, h, p, n, chunk):
    from repro.kernels.ssm_scan import kernel as K, ref as R
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (b, t, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, t, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, t, n)) * 0.5
    D = jnp.ones((h,)) * 0.5
    st = jax.random.normal(jax.random.key(8), (b, h, p, n)) * 0.1
    y1, s1 = R.ssd_ref(x, dt, A, Bm, Cm, D, st)
    y2, s2 = K.ssd_pallas(x, dt, A, Bm, Cm, D, st, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-4, rtol=2e-4)


def test_ssd_chunked_ops_matches_sequential():
    from repro.kernels.ssm_scan import ops, ref
    ks = jax.random.split(jax.random.key(5), 5)
    b, t, h, p, n = 2, 50, 2, 8, 8
    x = jax.random.normal(ks[0], (b, t, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, t, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, t, n)) * 0.5
    D = jnp.zeros((h,))
    st = jnp.zeros((b, h, p, n))
    y1, s1 = ref.ssd_ref(x, dt, A, Bm, Cm, D, st)
    y2, s2 = ops.ssd_chunked(x, dt, A, Bm, Cm, D, st, chunk=16)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# uct_select
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,a", [(7, 4), (300, 8), (64, 130), (1, 2)])
def test_uct_argmax_kernel(r, a):
    from repro.kernels.uct_select import ops as uo
    ks = jax.random.split(jax.random.key(6), 4)
    n = jax.random.randint(ks[0], (r, a), 0, 50).astype(jnp.float32)
    w = jax.random.normal(ks[1], (r, a)) * 3
    vl = jax.random.randint(ks[2], (r, a), 0, 3).astype(jnp.float32)
    pn = n.sum(-1) + 1
    valid = jax.random.bernoulli(ks[3], 0.8, (r, a)).at[:, 0].set(True)
    a1 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, use_ref=True)
    a2 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, interpret=True)
    assert bool((a1 == a2).all())


# The row shapes the lockstep Select stage issues (DESIGN.md §11): one
# [lanes, A] launch per tree level, where many rows repeat the SAME parent's
# child stats (co-located lanes), ``valid`` is ragged across rows, and
# finished lanes contribute all-invalid rows.
@pytest.mark.parametrize("lanes,a", [(8, 4), (12, 4), (16, 8), (32, 130)])
def test_uct_argmax_kernel_wave_duplicated_parents(lanes, a):
    from repro.kernels.uct_select import ops as uo
    ks = jax.random.split(jax.random.key(13), 4)
    # 3 distinct parents, each duplicated over ceil(lanes/3) co-located lanes
    parents_n = jax.random.randint(ks[0], (3, a), 0, 50).astype(jnp.float32)
    parents_w = jax.random.normal(ks[1], (3, a)) * 3
    rows = jnp.arange(lanes) % 3
    n, w = parents_n[rows], parents_w[rows]
    vl = jax.random.randint(ks[2], (lanes, a), 0, 3).astype(jnp.float32)
    pn = n.sum(-1) + 1
    valid = jax.random.bernoulli(ks[3], 0.7, (lanes, a)).at[:, 0].set(True)
    a1 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, use_ref=True)
    a2 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, interpret=True)
    assert bool((a1 == a2).all())
    # identical rows with identical masks pick identical children
    same = np.asarray(rows[:, None] == rows[None, :])
    eq_mask = np.asarray((valid[:, None, :] == valid[None, :, :]).all(-1))
    eq_vl = np.asarray((vl[:, None, :] == vl[None, :, :]).all(-1))
    out = np.asarray(a2)
    ii, jj = np.nonzero(same & eq_mask & eq_vl)
    assert (out[ii] == out[jj]).all()


def test_uct_argmax_kernel_wave_finished_lanes():
    """All-invalid rows (finished/masked lanes) return 0 on both paths, and
    an entirely-finished wave — the all-lanes-done edge — is well defined."""
    from repro.kernels.uct_select import ops as uo
    lanes, a = 8, 4
    ks = jax.random.split(jax.random.key(14), 3)
    n = jax.random.randint(ks[0], (lanes, a), 0, 9).astype(jnp.float32)
    w = jax.random.normal(ks[1], (lanes, a))
    vl = jnp.zeros((lanes, a))
    pn = n.sum(-1) + 1
    half = jnp.arange(lanes)[:, None] < 4        # lanes 4.. are finished
    valid = jnp.broadcast_to(half, (lanes, a))
    a1 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, use_ref=True)
    a2 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=valid, interpret=True)
    assert bool((a1 == a2).all())
    assert bool((a2[4:] == 0).all())
    none = jnp.zeros((lanes, a), bool)
    z1 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=none, use_ref=True)
    z2 = uo.uct_argmax(n, w, vl, pn, cp=1.4, valid=none, interpret=True)
    assert bool((z1 == 0).all()) and bool((z2 == 0).all())


# Must-explore sentinel ordering (uct.py docstring): idle unvisited children
# score 1e30 and win; sentinel ties resolve FIRST-MAX — the lowest valid
# index — identically on the ref and Pallas paths, in both vl modes.
@pytest.mark.parametrize("vl_mode", ["loss", "wu"])
@pytest.mark.parametrize("r,a", [(8, 4), (64, 130)])
def test_uct_argmax_multiple_unvisited_tie_lowest_index(vl_mode, r, a):
    from repro.kernels.uct_select import ops as uo
    ks = jax.random.split(jax.random.key(15), 3)
    n = jax.random.randint(ks[0], (r, a), 0, 9).astype(jnp.float32)
    # every row gets >= 2 idle unvisited children at random columns
    cols = jax.random.permutation(
        ks[1], jnp.broadcast_to(jnp.arange(a), (r, a)), axis=1,
        independent=True)[:, :2]
    rows = jnp.arange(r)[:, None]
    n = n.at[rows, cols].set(0.0)
    w = jax.random.normal(ks[2], (r, a))
    zero = jnp.zeros((r, a))
    pn = n.sum(-1) + 1
    valid = jnp.ones((r, a), bool)
    kw = dict(cp=1.4, valid=valid, child_o=zero, vl_mode=vl_mode)
    a1 = uo.uct_argmax(n, w, zero, pn, use_ref=True, **kw)
    a2 = uo.uct_argmax(n, w, zero, pn, interpret=True, **kw)
    assert bool((a1 == a2).all())
    # first-max: the winner is the LOWEST-index unvisited child
    expect = np.asarray(jnp.argmax(n == 0.0, axis=-1))
    assert (np.asarray(a2) == expect).all()
    # masking the lowest unvisited column moves the tie to the next one
    valid2 = valid.at[rows[:, 0], expect].set(False)
    kw["valid"] = valid2
    b1 = uo.uct_argmax(n, w, zero, pn, use_ref=True, **kw)
    b2 = uo.uct_argmax(n, w, zero, pn, interpret=True, **kw)
    assert bool((b1 == b2).all())
    assert not (np.asarray(b2) == expect).any()


@pytest.mark.parametrize("r,a", [(7, 4), (300, 8), (64, 130), (1, 2)])
def test_uct_argmax_kernel_wu_mode(r, a):
    """WU-UCT scoring (vl_mode="wu"): the O operand feeds exploration only.
    Ref and Pallas agree bit-for-bit; the vloss operand is ignored; with
    O == 0 the wu ranking falls back to loss-with-no-vloss exactly."""
    from repro.kernels.uct_select import ops as uo
    ks = jax.random.split(jax.random.key(16), 5)
    n = jax.random.randint(ks[0], (r, a), 0, 50).astype(jnp.float32)
    w = jax.random.normal(ks[1], (r, a)) * 3
    vl = jax.random.randint(ks[2], (r, a), 0, 3).astype(jnp.float32)
    o = jax.random.randint(ks[3], (r, a), 0, 5).astype(jnp.float32)
    pn = n.sum(-1) + 1 + o.sum(-1)
    valid = jax.random.bernoulli(ks[4], 0.8, (r, a)).at[:, 0].set(True)
    kw = dict(cp=1.4, valid=valid, vl_mode="wu")
    a1 = uo.uct_argmax(n, w, vl, pn, child_o=o, use_ref=True, **kw)
    a2 = uo.uct_argmax(n, w, vl, pn, child_o=o, interpret=True, **kw)
    assert bool((a1 == a2).all())
    # vloss never reaches the wu formula
    a3 = uo.uct_argmax(n, w, vl * 0, pn, child_o=o, interpret=True, **kw)
    assert bool((a2 == a3).all())
    # O == 0 and vloss == 0: both modes compute the same scores
    z = jnp.zeros((r, a))
    wu0 = uo.uct_argmax(n, w, z, pn, child_o=z, interpret=True, **kw)
    ls0 = uo.uct_argmax(n, w, z, pn, cp=1.4, valid=valid, interpret=True)
    assert bool((wu0 == ls0).all())


def test_uct_argmax_kernel_wu_all_masked_rows():
    """The all-lanes-done edge under wu mode: fully-masked rows return 0 on
    both paths, matching the loss-mode contract."""
    from repro.kernels.uct_select import ops as uo
    lanes, a = 8, 4
    ks = jax.random.split(jax.random.key(17), 3)
    n = jax.random.randint(ks[0], (lanes, a), 0, 9).astype(jnp.float32)
    w = jax.random.normal(ks[1], (lanes, a))
    o = jax.random.randint(ks[2], (lanes, a), 0, 4).astype(jnp.float32)
    pn = n.sum(-1) + 1 + o.sum(-1)
    none = jnp.zeros((lanes, a), bool)
    kw = dict(cp=1.4, valid=none, child_o=o, vl_mode="wu")
    z1 = uo.uct_argmax(n, w, o * 0, pn, use_ref=True, **kw)
    z2 = uo.uct_argmax(n, w, o * 0, pn, interpret=True, **kw)
    assert bool((z1 == 0).all()) and bool((z2 == 0).all())


# Running-assignment kernel (DESIGN.md §16): the fori_loop scan over rows
# must agree with the jnp reference on the exact boards the lockstep Select
# stage issues — duplicated parents, ragged/odd row counts (the 8-row pad
# path), finished lanes interleaved with active ones, and sentinel ties.
@pytest.mark.parametrize("vl_mode", ["loss", "wu"])
@pytest.mark.parametrize("lanes,a", [(7, 4), (8, 4), (12, 8), (16, 130)])
def test_uct_argmax_running_kernel_duplicated_parents(vl_mode, lanes, a):
    from repro.kernels.uct_select import ops as uo
    ks = jax.random.split(jax.random.key(18), 5)
    gn = jax.random.randint(ks[0], (3, a), 0, 50).astype(jnp.float32)
    gw = jax.random.normal(ks[1], (3, a)) * 3
    gv = jax.random.randint(ks[2], (3, a), 0, 3).astype(jnp.float32)
    go = jax.random.randint(ks[3], (3, a), 0, 4).astype(jnp.float32)
    rows = (jnp.arange(lanes) % 3).astype(jnp.int32)
    n, w, vl, o = gn[rows], gw[rows], gv[rows], go[rows]
    pn = n.sum(-1) + vl.sum(-1) + o.sum(-1) + 1
    valid = jax.random.bernoulli(ks[4], 0.7, (3, a)).at[:, 0].set(True)[rows]
    kw = dict(cp=1.4, valid=valid, child_o=o, vl_mode=vl_mode)
    a1 = uo.uct_argmax_running(n, w, vl, pn, rows, use_ref=True, **kw)
    a2 = uo.uct_argmax_running(n, w, vl, pn, rows, interpret=True, **kw)
    assert bool((a1 == a2).all())


@pytest.mark.parametrize("vl_mode", ["loss", "wu"])
def test_uct_argmax_running_kernel_skips_finished_lanes(vl_mode):
    """Finished (all-invalid) lanes interleaved with active co-located ones:
    they return 0 AND contribute nothing to later lanes' deltas — the active
    lanes still take distinct unvisited children as if the wave were dense.
    The entirely-finished wave returns all zeros on both paths."""
    from repro.kernels.uct_select import ops as uo
    lanes, a = 8, 6
    z = jnp.zeros((lanes, a))
    pn = jnp.ones((lanes,))
    act = (jnp.arange(lanes) % 2) == 0            # lanes 1,3,5,7 finished
    valid = jnp.broadcast_to(act[:, None], (lanes, a))
    rows = jnp.zeros((lanes,), jnp.int32)         # one shared parent
    kw = dict(cp=0.7, valid=valid, child_o=z, vl_mode=vl_mode)
    a1 = uo.uct_argmax_running(z, z, z, pn, rows, use_ref=True, **kw)
    a2 = uo.uct_argmax_running(z, z, z, pn, rows, interpret=True, **kw)
    assert bool((a1 == a2).all())
    out = np.asarray(a2)
    assert (out[1::2] == 0).all()
    # active lanes disperse over the unvisited children, skipping the holes
    assert sorted(out[::2].tolist()) == [0, 1, 2, 3]
    none = jnp.zeros((lanes, a), bool)
    kw["valid"] = none
    z1 = uo.uct_argmax_running(z, z, z, pn, rows, use_ref=True, **kw)
    z2 = uo.uct_argmax_running(z, z, z, pn, rows, interpret=True, **kw)
    assert bool((z1 == 0).all()) and bool((z2 == 0).all())


@pytest.mark.parametrize("vl_mode", ["loss", "wu"])
def test_uct_argmax_running_must_explore_sentinel_rotates(vl_mode):
    """Sentinel ties under the running delta: the first lane of a co-located
    pair takes the LOWEST-index idle unvisited child (first-max); its pick
    raises that child's effective count past the 0.5 threshold, so the
    second lane's sentinel moves to the OTHER unvisited child."""
    from repro.kernels.uct_select import ops as uo
    a = 5
    unv = {0: (1, 3), 1: (0, 4)}                  # parent -> unvisited cols
    gn = np.full((2, a), 7.0, np.float32)
    for p, cols in unv.items():
        gn[p, list(cols)] = 0.0
    rows = jnp.asarray([0, 0, 1, 1], jnp.int32)
    n = jnp.asarray(gn)[rows]
    w = jnp.asarray(np.random.default_rng(19).normal(size=(2, a)),
                    jnp.float32)[rows]
    z = jnp.zeros((4, a))
    pn = n.sum(-1) + 1
    valid = jnp.ones((4, a), bool)
    kw = dict(cp=1.4, valid=valid, child_o=z, vl_mode=vl_mode)
    a1 = uo.uct_argmax_running(n, w, z, pn, rows, use_ref=True, **kw)
    a2 = uo.uct_argmax_running(n, w, z, pn, rows, interpret=True, **kw)
    assert bool((a1 == a2).all())
    assert np.asarray(a2).tolist() == [1, 3, 0, 4]


# ---------------------------------------------------------------------------
# flash backward (custom VJP) vs autodiff-through-sdpa
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cap", [0.0, 30.0])
@pytest.mark.parametrize("dv", [16, 8])
def test_blocked_attention_grads(cap, dv):
    from repro.models import layers as L
    ks = jax.random.split(jax.random.key(7), 4)
    b, s, h, hkv, d = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, dv))
    t = jax.random.normal(ks[3], (b, s, h, dv))
    f1 = lambda q, k, v: (L.sdpa(q, k, v, causal=True, logits_soft_cap=cap) * t).sum()
    f2 = lambda q, k, v: (L.blocked_attention(
        q, k, v, causal=True, blk_q=32, blk_k=16, logits_soft_cap=cap) * t).sum()
    o1, g1 = jax.value_and_grad(f1, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(f2, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(o1 - o2)) < 1e-3
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), atol=1e-4, rtol=1e-3)
