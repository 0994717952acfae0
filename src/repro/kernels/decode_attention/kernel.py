"""Pallas TPU flash-decode: one query row vs. a long KV cache.

Grid = (B*H, Skv/BLK_K), kv dimension sequential with (m, l, acc) VMEM
scratch.  Per-sequence valid lengths arrive via scalar prefetch (SMEM) so
fully-invalid kv blocks are skipped — the split-K flash-decode pattern of the
decode_32k / long_500k serving cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params

NEG_INF = -1e30


def _dec_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale: float, blk_k: int, n_heads: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    b = bh // n_heads
    valid = valid_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * blk_k

    @pl.when(k_start < valid)
    def _body():
        q = q_ref[0].astype(jnp.float32)                      # [1, D]
        k = k_ref[0].astype(jnp.float32)                      # [BLK_K, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_bhd(q, k, v, valid_len, *, scale: float,
                         blk_k: int = 512, interpret: bool = False):
    """q [BH, 1, D]; k, v [BHkv, Sk, D]; valid_len [B] i32 -> [BH, 1, D]."""
    bh, _, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    nb = valid_len.shape[0]
    n_heads = bh // nb
    nk = pl.cdiv(sk, blk_k)

    kernel = functools.partial(_dec_kernel, scale=scale, blk_k=blk_k,
                               n_heads=n_heads)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, ki, v_: (b, 0, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, ki, v_: (b // group, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, ki, v_: (b // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, ki, v_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="decode_attention",
    )(valid_len, q, k, v)
