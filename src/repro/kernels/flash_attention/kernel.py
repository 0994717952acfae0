"""Pallas TPU flash attention (fwd): blocked online softmax, causal, GQA.

Grid = (B*H, Sq/BLK_Q, Sk/BLK_K); the last (kv) dimension is ``ARBITRARY``
(sequential) so the per-(head, q-block) running max / denom / accumulator
scratch persists across kv steps — the canonical TPU flash pattern.  GQA is
handled in the kv index_map (no materialized head repetition).  MXU dims are
kept 128-aligned by the ops-layer padding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, blk_q: int, blk_k: int,
               seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    # skip kv blocks entirely above the causal diagonal
    run = (not causal) or (q_start + blk_q - 1 >= k_start)
    run_pred = jnp.asarray(True) if not causal else (q_start + blk_q - 1 >= k_start)

    @pl.when(run_pred)
    def _body():
        q = q_ref[0].astype(jnp.float32)                      # [BLK_Q, D]
        k = k_ref[0].astype(jnp.float32)                      # [BLK_K, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        # mask kv padding (seq_k may be < padded length)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < seq_k, s, NEG_INF)

        m_prev = m_scr[...]                                   # [BLK_Q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool, scale: float,
                         blk_q: int = 128, blk_k: int = 128,
                         seq_k_valid: int = None, interpret: bool = False):
    """q [BH, Sq, D]; k, v [BHkv, Sk, D]; returns [BH, Sq, D].

    Sq/Sk must be multiples of the block sizes (ops layer pads).
    """
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    nq, nk = sq // blk_q, sk // blk_k
    seq_k = seq_k_valid if seq_k_valid is not None else sk

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
        seq_k=seq_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki: (b // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
