"""``vocab_top_k`` equals ``jax.lax.top_k``, values and indices bitwise,
however the search wraps it: alone, under one or two vmaps, inside a scan
body, and closed over by a vmap its operand is not batched in.  The logits
are rounded to bf16 with equal maxima planted in every row, so the order of
tied entries is tested too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.domains.lm_decode import vocab_top_k


def _alone(f, x):
    return f(x[0, 0])


def _vmap(f, x):
    return jax.vmap(f)(x[0])


def _vmap2(f, x):
    return jax.vmap(jax.vmap(f))(x)


def _scan(f, x):
    return jax.lax.scan(lambda c, row: (c, jax.vmap(f)(row)), 0, x)[1]


def _closed_over(f, x):
    # the inner vmap's axis is not the operand's: only the outer one is
    return jax.vmap(lambda row: jax.vmap(lambda _: f(row))(
        jnp.arange(3)))(x[0])


def _logits(vocab, seed=0):
    x = np.random.default_rng(seed).standard_normal((3, 4, vocab))
    x = np.array(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    top = float(jnp.bfloat16(x.max() + 1.0))
    for i, j in np.ndindex(x.shape[:2]):
        x[i, j, [7 * (i + 1) + j, vocab // 2 + i, vocab - 1 - j]] = top
    return jnp.asarray(x)


@pytest.mark.parametrize("vocab", (512, 151936))
@pytest.mark.parametrize("k", (1, 4, 8))
@pytest.mark.parametrize("wrap", (_alone, _vmap, _vmap2, _scan, _closed_over),
                         ids=("alone", "vmap", "vmap-vmap", "scan",
                              "closed-over"))
def test_vocab_top_k_equals_lax_top_k(wrap, k, vocab):
    x = _logits(vocab)
    got = jax.jit(lambda x: wrap(lambda r: vocab_top_k(r, k), x))(x)
    want = jax.jit(lambda x: wrap(lambda r: jax.lax.top_k(r, k), x))(x)
    assert isinstance(got, tuple) and len(got) == 2
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the planted maxima lead every row, lowest index first
    idx = np.asarray(got[1]).reshape(-1, k)
    assert (np.diff(idx[:, :min(k, 3)], axis=1) > 0).all()
