"""Shared neural-net building blocks (pure functions over param dicts).

Conventions
-----------
* activations are ``cfg.jdtype`` (bf16), norm/softmax accumulate in fp32;
* attention layouts are [B, S, H, D];
* per-layer params may be stacked on a leading ``layers`` axis for lax.scan.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.base import ModelConfig


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, in_axis: int = 0):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype, std: float = 0.02):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(cfg: ModelConfig, key, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), cfg.jdtype), "bias": jnp.zeros((d,), cfg.jdtype)}
    return {"scale": jnp.ones((d,), cfg.jdtype)}


def apply_norm(cfg: ModelConfig, p, x):
    if "bias" in p:
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# rotary position embedding (supports partial rotary, stablelm-2 style)
# ---------------------------------------------------------------------------
def rope_freqs(cfg: ModelConfig, positions, rot_dim: Optional[int] = None):
    """positions [..., S] -> (cos, sin) each [..., S, rot_dim/2] fp32."""
    rot = rot_dim or int(cfg.head_dim * cfg.rope_frac)
    rot = max(rot - rot % 2, 2)
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, rope_frac: float = 1.0):
    """x [B,S,H,D]; cos/sin [B,S,R/2] or [S,R/2]. Rotates leading R dims of D."""
    r2 = cos.shape[-1]
    rot, x_pass = x[..., : 2 * r2], x[..., 2 * r2:]
    x1, x2 = rot[..., :r2], rot[..., r2:]
    if cos.ndim == 2:  # [S, R/2] -> broadcast over batch and heads
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    else:              # [B, S, R/2]
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * cos_b - x2f * sin_b
    o2 = x2f * cos_b + x1f * sin_b
    out = jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if x_pass.shape[-1] else out


# ---------------------------------------------------------------------------
# attention (reference path; kernel path lives in repro.kernels.*.ops)
# ---------------------------------------------------------------------------
def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def sdpa(q, k, v, *, causal: bool, q_offset=0, bias=None, logits_soft_cap: float = 0.0):
    """Reference scaled-dot-product attention.

    q [B,Sq,H,D], k/v [B,Sk,Hkv,D]; GQA via kv-head repetition.
    ``q_offset`` positions q rows at kv index offset (decode / chunked prefill).
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if logits_soft_cap > 0.0:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    if bias is not None:
        logits = logits + bias
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        mask = qpos >= kpos
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _fa_bias(qi, ki, blk_q, blk_k, sk, q_offset, causal):
    """Additive mask bias [blk_q, blk_k] f32 (0 keep / -1e30 drop).

    Additive form (not jnp.where on the scores) so differentiation of the
    surrounding scans never saves a batch-broadcast boolean mask as a
    residual — add's transpose is residual-free.
    """
    kpos = ki * blk_k + jnp.arange(blk_k)
    keep = (kpos[None, :] < sk) * jnp.ones((blk_q, 1), bool)
    if causal:
        qpos = qi * blk_q + jnp.arange(blk_q) + q_offset
        keep = keep & (qpos[:, None] >= kpos[None, :])
    return jnp.where(keep, 0.0, -1e30).astype(jnp.float32)


def _fa_scores(qb, kb, scale, cap):
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    if cap > 0.0:
        s = cap * jnp.tanh(s / cap)
    return s


def _blocked_fwd(q, k, v, causal, q_offset, blk_q, blk_k, cap):
    """Returns (out [B,Sq,H,Dv], lse [B,Hkv,g,Sq]). Supports Dv != Dqk (MLA)."""
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    pad_q, pad_k = (-sq) % blk_q, (-sk) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    nq, nk = (sq + pad_q) // blk_q, (sk + pad_k) // blk_k
    qs = qp.reshape(b, nq, blk_q, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, nk, blk_k, hkv, d).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(b, nk, blk_k, hkv, dv).transpose(1, 0, 2, 3, 4)

    def q_block(_, qi_qb):
        qi, qb = qi_qb
        m0 = jnp.full((b, hkv, g, blk_q), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, blk_q), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, blk_q, dv), jnp.float32)

        def kv_block(carry, ki_kb):
            m, l, acc = carry
            ki, kb, vb = ki_kb
            s = _fa_scores(qb, kb, scale, cap)
            s = s + _fa_bias(qi, ki, blk_q, blk_k, sk, q_offset, causal)[None, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0),
                                      (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.transpose(0, 3, 1, 2, 4).astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pad_q, h, dv)[:, :sq]
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, hkv, g, sq + pad_q)[..., :sq]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _blocked_attention_core(q, k, v, causal, q_offset, blk_q, blk_k, cap):
    return _blocked_fwd(q, k, v, causal, q_offset, blk_q, blk_k, cap)[0]


def _core_fwd(q, k, v, causal, q_offset, blk_q, blk_k, cap):
    out, lse = _blocked_fwd(q, k, v, causal, q_offset, blk_q, blk_k, cap)
    return out, (q, k, v, out, lse)


def _core_bwd(causal, q_offset, blk_q, blk_k, cap, res, dout):
    """Flash backward: recompute p blockwise from (q, k, v, lse); no stored
    probability matrices (the TPU flash-bwd dataflow, in XLA form)."""
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    pad_q, pad_k = (-sq) % blk_q, (-sk) % blk_k
    pq = lambda x: jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else x
    pk = lambda x: jnp.pad(x, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else x
    qp, dop, op = pq(q), pq(dout), pq(out)
    kp, vp = pk(k), pk(v)
    nq, nk = (sq + pad_q) // blk_q, (sk + pad_k) // blk_k
    delta = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32), -1)  # [B,Sq,H]
    delta = delta.reshape(b, nq, blk_q, hkv, g).transpose(1, 0, 3, 4, 2)
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, pad_q))) if pad_q else lse
    lse_b = lse_p.reshape(b, hkv, g, nq, blk_q).transpose(3, 0, 1, 2, 4)
    qs = qp.reshape(b, nq, blk_q, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    dos = dop.reshape(b, nq, blk_q, hkv, g, dv).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, nk, blk_k, hkv, d).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(b, nk, blk_k, hkv, dv).transpose(1, 0, 2, 3, 4)

    def q_block(carry, inp):
        dk_acc, dv_acc = carry                     # [B, Sk_pad, Hkv, D] f32
        qi, qb, dob, lse_i, delta_i = inp

        def kv_block(c2, inp2):
            dq_b, dk_a, dv_a = c2
            ki, kb, vb = inp2
            bias = _fa_bias(qi, ki, blk_q, blk_k, sk, q_offset, causal)
            s = _fa_scores(qb, kb, scale, cap) + bias[None, None, None]
            p = jnp.exp(s - lse_i[..., None])                       # [B,h,g,q,k]
            dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, dob.astype(jnp.float32))
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dob, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_i[..., None])                      # wrt capped s
            if cap > 0.0:
                ds = ds * (1.0 - jnp.square((s - bias[None, None, None]) / cap))
            ds = ds * (bias[None, None, None] > -1.0)               # re-mask
            dq_b = dq_b + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb,
                                     preferred_element_type=jnp.float32) * scale
            dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                qb.astype(jnp.float32)) * scale
            dk_a = jax.lax.dynamic_update_slice(
                dk_a, jax.lax.dynamic_slice(
                    dk_a, (0, ki * blk_k, 0, 0), (b, blk_k, hkv, d)) + dk_blk,
                (0, ki * blk_k, 0, 0))
            dv_a = jax.lax.dynamic_update_slice(
                dv_a, jax.lax.dynamic_slice(
                    dv_a, (0, ki * blk_k, 0, 0), (b, blk_k, hkv, dv)) + dv_blk,
                (0, ki * blk_k, 0, 0))
            return (dq_b, dk_a, dv_a), None

        dq0 = jnp.zeros((b, blk_q, hkv, g, d), jnp.float32)
        (dq_b, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), (jnp.arange(nk), ks, vs))
        return (dk_acc, dv_acc), dq_b

    dkv0 = (jnp.zeros((b, sk + pad_k, hkv, d), jnp.float32),
            jnp.zeros((b, sk + pad_k, hkv, dv), jnp.float32))
    (dk, dv), dqs = jax.lax.scan(q_block, dkv0,
                                 (jnp.arange(nq), qs, dos, lse_b, delta))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pad_q, h, d)[:, :sq]
    return (dq.astype(q.dtype), dk[:, :sk].astype(k.dtype),
            dv[:, :sk].astype(v.dtype))


_blocked_attention_core.defvjp(_core_fwd, _core_bwd)


def blocked_attention(q, k, v, *, causal: bool, q_offset=0, blk_q=256,
                      blk_k=1024, logits_soft_cap: float = 0.0):
    """Memory-efficient attention in pure jnp: double-blocked online softmax
    with a flash *backward* (custom VJP; p recomputed blockwise — never
    materializes [Sq, Sk] in fwd or bwd).

    Used by the full-size configs so the dry-run's lowered HLO has flash-like
    memory behaviour; the Pallas kernel replaces it 1:1 on real TPU.
    GQA is computed grouped (no kv-head repetition).
    """
    sq, sk = q.shape[1], k.shape[1]
    return _blocked_attention_core(q, k, v, causal, int(q_offset),
                                   min(blk_q, sq), min(blk_k, sk),
                                   float(logits_soft_cap))


def attention(cfg: ModelConfig, q, k, v, *, causal: bool, q_offset=0,
              kv_valid_len=None, logits_soft_cap: float = 0.0,
              kernels: bool = False):
    """Dispatch: Pallas flash kernels where ``kernels`` and ``cfg.pallas``
    allow, blocked or materialized jnp reference otherwise.

    ``kv_valid_len`` [B] masks a pre-allocated KV cache beyond the filled
    prefix (decode path).  ``kernels`` marks an inference caller: the
    kernels have no VJP, so a call that may be differentiated leaves it
    False.  They implement neither a logit soft cap nor a query offset.
    """
    if kernels and cfg.pallas and not logits_soft_cap:
        if kv_valid_len is None and q.shape[1] > 1 and q_offset == 0:
            from repro.kernels.flash_attention import ops as fa
            return fa.flash_attention(q, k, v, causal=causal)
        if q.shape[1] == 1 and kv_valid_len is not None:
            from repro.kernels.decode_attention import ops as da
            return da.decode_attention(q, k, v, kv_valid_len)
    if cfg.attn_impl == "blocked" and kv_valid_len is None and q.shape[1] > 1:
        return blocked_attention(q, k, v, causal=causal, q_offset=q_offset,
                                 blk_q=cfg.attn_blk_q, blk_k=cfg.attn_blk_k,
                                 logits_soft_cap=logits_soft_cap)
    bias = None
    if kv_valid_len is not None:
        kpos = jnp.arange(k.shape[1])[None, :]
        keep = kpos < kv_valid_len[:, None]
        bias = jnp.where(keep, 0.0, -jnp.inf)[:, None, None, :]
    return sdpa(q, k, v, causal=causal, q_offset=q_offset, bias=bias,
                logits_soft_cap=logits_soft_cap)


def init_gqa(cfg: ModelConfig, key):
    """Standard (non-MLA) GQA projection params."""
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), cfg.jdtype),
        "wk": dense_init(ks[1], (d, hkv * hd), cfg.jdtype),
        "wv": dense_init(ks[2], (d, hkv * hd), cfg.jdtype),
        "wo": dense_init(ks[3], (h * hd, d), cfg.jdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.jdtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.jdtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.jdtype)
    return p


def gqa_project_qkv(cfg: ModelConfig, p, x):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, s, h, hd), k.reshape(b, s, hkv, hd), v.reshape(b, s, hkv, hd))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":
        return {"wi": dense_init(ks[0], (d, f), cfg.jdtype),
                "bi": jnp.zeros((f,), cfg.jdtype),
                "wo": dense_init(ks[1], (f, d), cfg.jdtype),
                "bo": jnp.zeros((d,), cfg.jdtype)}
    return {"wg": dense_init(ks[0], (d, f), cfg.jdtype),
            "wu": dense_init(ks[1], (d, f), cfg.jdtype),
            "wd": dense_init(ks[2], (f, d), cfg.jdtype)}


def apply_mlp(cfg: ModelConfig, p, x):
    if "wi" in p:
        return jax.nn.gelu((x @ p["wi"] + p["bi"]).astype(jnp.float32)).astype(x.dtype) @ p["wo"] + p["bo"]
    return (jax.nn.silu((x @ p["wg"]).astype(jnp.float32)).astype(x.dtype) * (x @ p["wu"])) @ p["wd"]


# ---------------------------------------------------------------------------
# embedding + chunked cross-entropy (never materializes [B,S,V] fp32 at once)
# ---------------------------------------------------------------------------
def init_embed(cfg: ModelConfig, key):
    p = {"tok": embed_init(key, (cfg.vocab_size, cfg.d_model), cfg.jdtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab_size), cfg.jdtype)
    return p


def embed_tokens(cfg: ModelConfig, p, tokens):
    from repro.parallel.sharding import with_logical_constraint
    out = jnp.take(p["tok"], tokens, axis=0)
    return with_logical_constraint(out, ("batch",) + (None,) * (out.ndim - 1))


def lm_head(cfg: ModelConfig, p, x):
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    return (x @ w) * cfg.logit_scale


def chunked_softmax_xent(cfg: ModelConfig, p, x, labels, mask=None):
    """Mean next-token cross-entropy, computed in seq-chunks of ``cfg.ce_chunk``.

    x [B,S,D] (pre-head hidden), labels [B,S] int32, mask [B,S] {0,1}.
    Avoids materializing the full [B,S,V] logits in fp32: each chunk's logits
    live only inside its scan step (XLA frees between steps; with remat the
    backward recomputes per chunk as well).
    """
    b, s, d = x.shape
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    chunk = min(cfg.ce_chunk, s)
    n = s // chunk
    rem = s - n * chunk
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def one(xc, yc, mc):
        # remat: per-chunk logits are recomputed in the backward pass, so no
        # [B, chunk, V] fp32 buffer is ever saved across chunks.
        from repro.parallel.sharding import with_logical_constraint
        xc = with_logical_constraint(xc, ("batch", None, None))
        logits = (xc @ w).astype(jnp.float32) * cfg.logit_scale   # [B,c,V]
        logits = with_logical_constraint(logits, ("batch", None, "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * mc), jnp.sum(mc)

    def body(carry, args):
        tot, cnt = carry
        l, c = one(*args)
        return (tot + l, cnt + c), None

    xs = (x[:, : n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3),
          labels[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2),
          mask[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2))
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    if rem:
        l, c = one(x[:, n * chunk:], labels[:, n * chunk:], mask[:, n * chunk:])
        tot, cnt = tot + l, cnt + c
    return tot / jnp.maximum(cnt, 1.0)
