"""Zamba2 hybrid (arXiv:2411.15242): Mamba-2 backbone + shared attention block.

* ``cfg.n_layers`` Mamba-2 (SSD) blocks at width D;
* one **shared** transformer block (attention + MLP) at width 2D, applied after
  every ``cfg.shared_attn_every`` Mamba blocks on ``concat(hidden, embed0)``
  with per-application LoRA deltas on the QKV projections, projected back to D;
* decode state: per-block conv + SSD states (O(1) in context) plus one KV cache
  per shared-block application (the only context-length-dependent memory).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan import ops as ssd_ops
from repro.models import layers as L
from repro.models.base import ModelConfig, register_family

LORA_RANK = 64


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    h_ssm = d_inner // cfg.ssm_head_dim
    d_conv = d_inner + 2 * cfg.ssm_state          # conv covers x, B, C
    return d_inner, h_ssm, d_conv


def _n_apps(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.shared_attn_every


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_mamba_block(cfg: ModelConfig, key):
    d = cfg.d_model
    d_inner, h_ssm, d_conv = _dims(cfg)
    n = cfg.ssm_state
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    return {
        "norm": {"scale": jnp.ones((d,), dt)},
        "in_proj": L.dense_init(ks[0], (d, 2 * d_inner + 2 * n + h_ssm), dt),
        "conv_w": L.dense_init(ks[1], (cfg.ssm_conv_width, d_conv), dt),
        "conv_b": jnp.zeros((d_conv,), dt),
        "dt_bias": jnp.zeros((h_ssm,), dt),
        "A_log": jnp.zeros((h_ssm,), jnp.float32),       # A = -exp(A_log)
        "D": jnp.ones((h_ssm,), jnp.float32),
        "gate_norm": {"scale": jnp.ones((d_inner,), dt)},
        "out_proj": L.dense_init(ks[2], (d_inner, d), dt),
    }


def _init_shared_block(cfg: ModelConfig, key):
    d2 = 2 * cfg.d_model
    h, hd = cfg.n_heads, cfg.head_dim                     # at width 2D
    ks = jax.random.split(key, 9)
    dt = cfg.jdtype
    napps = _n_apps(cfg)
    return {
        "ln1": {"scale": jnp.ones((d2,), dt)},
        "wq": L.dense_init(ks[0], (d2, h * hd), dt),
        "wk": L.dense_init(ks[1], (d2, cfg.kv_heads * hd), dt),
        "wv": L.dense_init(ks[2], (d2, cfg.kv_heads * hd), dt),
        "wo": L.dense_init(ks[3], (h * hd, d2), dt),
        "lora_a": (jax.random.normal(ks[4], (napps, 3, d2, LORA_RANK), jnp.float32) * 0.02).astype(dt),
        "lora_b": jnp.zeros((napps, 3, LORA_RANK, h * hd), dt),
        "ln2": {"scale": jnp.ones((d2,), dt)},
        "mlp": {"wg": L.dense_init(ks[5], (d2, cfg.d_ff), dt),
                "wu": L.dense_init(ks[6], (d2, cfg.d_ff), dt),
                "wd": L.dense_init(ks[7], (cfg.d_ff, d2), dt)},
        "out": L.dense_init(ks[8], (d2, cfg.d_model), dt),
    }


def init(cfg: ModelConfig, key):
    k_emb, k_m, k_s, k_f = jax.random.split(key, 4)
    stacked = jax.vmap(lambda k: _init_mamba_block(cfg, k))(
        jax.random.split(k_m, cfg.n_layers))
    return {
        "embed": L.init_embed(cfg, k_emb),
        "mamba": stacked,
        "shared": _init_shared_block(cfg, k_s),
        "final_norm": {"scale": jnp.ones((cfg.d_model,), cfg.jdtype)},
    }


def param_axes(cfg: ModelConfig):
    mb = {"norm": {"scale": (None,)},
          "in_proj": ("embed", "mlp"), "conv_w": (None, "mlp"), "conv_b": ("mlp",),
          "dt_bias": (None,), "A_log": (None,), "D": (None,),
          "gate_norm": {"scale": ("mlp",)}, "out_proj": ("mlp", "embed")}
    mb = jax.tree_util.tree_map(lambda ax: ("layers",) + ax, mb,
                                is_leaf=lambda x: isinstance(x, tuple))
    sh = {"ln1": {"scale": (None,)},
          "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
          "wo": ("heads", "embed"),
          "lora_a": (None, None, "embed", None), "lora_b": (None, None, None, "heads"),
          "ln2": {"scale": (None,)},
          "mlp": {"wg": ("embed", "mlp"), "wu": ("embed", "mlp"), "wd": ("mlp", "embed")},
          "out": ("embed", None)}
    emb = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        emb["head"] = ("embed", "vocab")
    return {"embed": emb, "mamba": mb, "shared": sh,
            "final_norm": {"scale": (None,)}}


# ---------------------------------------------------------------------------
# mamba block forward
# ---------------------------------------------------------------------------
def _causal_conv(x, w, b, conv_state=None):
    """Depthwise causal conv, width W. x [B,S,C]; w [W,C]; conv_state [B,W-1,C].

    Returns (y [B,S,C], new_conv_state [B,W-1,C]).
    """
    width = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([conv_state, x], axis=1)          # [B, S+W-1, C]
    y = sum(xp[:, i: i + x.shape[1]] * w[i] for i in range(width)) + b
    new_state = xp[:, -(width - 1):]
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), new_state


def _mamba_block(cfg: ModelConfig, p, x, state):
    """x [B,S,D]; state {conv [B,W-1,Cc], ssd [B,H,P,N]}."""
    from repro.parallel.sharding import with_logical_constraint
    x = with_logical_constraint(x, ("batch", None, None))
    b, s, d = x.shape
    d_inner, h_ssm, d_conv = _dims(cfg)
    n = cfg.ssm_state
    hres = x
    x = L.rmsnorm(x, p["norm"]["scale"])
    proj = x @ p["in_proj"]
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner: d_inner + d_conv]
    dt_raw = proj[..., d_inner + d_conv:]
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], state["conv"])
    xs = xbc[..., :d_inner].reshape(b, s, h_ssm, cfg.ssm_head_dim)
    Bm = xbc[..., d_inner: d_inner + n]
    Cm = xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"])
    y, new_ssd = ssd_ops.ssd(xs, dt, A, Bm, Cm, p["D"], state["ssd"],
                             use_pallas=cfg.pallas)
    y = y.reshape(b, s, d_inner)
    y = L.rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                  p["gate_norm"]["scale"])
    return hres + y @ p["out_proj"], {"conv": new_conv, "ssd": new_ssd}


def init_mamba_states(cfg: ModelConfig, batch_size: int):
    d_inner, h_ssm, d_conv = _dims(cfg)
    return {
        "conv": jnp.zeros((cfg.n_layers, batch_size, cfg.ssm_conv_width - 1, d_conv), cfg.jdtype),
        "ssd": jnp.zeros((cfg.n_layers, batch_size, h_ssm, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# shared attention block (width 2D), per-application LoRA
# ---------------------------------------------------------------------------
def _shared_qkv(cfg, p, h2, app_idx):
    b, s, _ = h2.shape
    hn, hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    la, lb = p["lora_a"][app_idx], p["lora_b"][app_idx]    # [3,2D,r],[3,r,H*hd]
    q = h2 @ p["wq"] + (h2 @ la[0]) @ lb[0]
    k = h2 @ p["wk"] + ((h2 @ la[1]) @ lb[1])[..., : hkv * hd]
    v = h2 @ p["wv"] + ((h2 @ la[2]) @ lb[2])[..., : hkv * hd]
    return (q.reshape(b, s, hn, hd), k.reshape(b, s, hkv, hd),
            v.reshape(b, s, hkv, hd))


def _shared_block(cfg: ModelConfig, p, h, emb0, app_idx, *, positions,
                  cache_kv=None, pos=None, kv_valid_len=None):
    """h [B,S,D] + emb0 [B,S,D] -> delta [B,S,D]; optional KV-cache decode."""
    b, s, _ = h.shape
    x2 = jnp.concatenate([h, emb0], axis=-1)               # [B,S,2D]
    y = L.rmsnorm(x2, p["ln1"]["scale"])
    q, k, v = _shared_qkv(cfg, p, y, app_idx)
    cos, sin = L.rope_freqs(cfg, positions)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    if cache_kv is not None:
        ck, cv = cache_kv
        ck = ck.at[jnp.arange(b), pos].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[jnp.arange(b), pos].set(v[:, 0].astype(cv.dtype))
        new_kv = (ck, cv)
        attn = L.attention(cfg, q, ck, cv, causal=False, kv_valid_len=kv_valid_len)
    else:
        new_kv = (k, v)          # full-seq KV (prefill collects these)
        attn = L.attention(cfg, q, k, v, causal=True)
    x2 = x2 + attn.reshape(b, s, -1) @ p["wo"]
    y = L.rmsnorm(x2, p["ln2"]["scale"])
    x2 = x2 + L.apply_mlp(cfg, p["mlp"], y)
    return x2 @ p["out"], new_kv


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------
def _slice_layers(tree, lo, hi):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


def _segments(cfg: ModelConfig):
    """[(start, end, apply_shared_after)] covering all mamba blocks."""
    segs = []
    step = cfg.shared_attn_every
    i = 0
    app = 0
    while i < cfg.n_layers:
        j = min(i + step, cfg.n_layers)
        has_app = (j - i == step) and (app < _n_apps(cfg))
        segs.append((i, j, app if has_app else None))
        if has_app:
            app += 1
        i = j
    return segs


def _run(cfg: ModelConfig, params, x, emb0, states, *, positions,
         shared_caches=None, pos=None, kv_valid_len=None):
    """states: stacked mamba states; shared_caches: {k,v} [n_apps,...] or None."""
    def seg_scan(x, seg_params, seg_states):
        def body(carry, xs):
            lp, st = xs
            y, new_st = _mamba_block(cfg, lp, carry, st)
            if cfg.seq_shard_carry and y.shape[1] > 1:
                from repro.parallel.sharding import with_logical_constraint
                y = with_logical_constraint(y, ("batch", "act_seq", None))
            return y, new_st
        if cfg.remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        return jax.lax.scan(body, x, (seg_params, seg_states))

    new_states = []
    new_shared = []
    for (lo, hi, app) in _segments(cfg):
        x, new_st = seg_scan(x, _slice_layers(params["mamba"], lo, hi),
                             _slice_layers(states, lo, hi))
        new_states.append(new_st)
        if app is not None:
            if shared_caches is not None:
                ckv = (shared_caches["k"][app], shared_caches["v"][app])
                delta, new_kv = _shared_block(
                    cfg, params["shared"], x, emb0, app, positions=positions,
                    cache_kv=ckv, pos=pos, kv_valid_len=kv_valid_len)
                new_shared.append(new_kv)
            else:
                delta, kvs = _shared_block(cfg, params["shared"], x, emb0, app,
                                           positions=positions)
                new_shared.append(kvs)
            x = x + delta
    states_out = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, 0), *new_states)
    return x, states_out, new_shared


def hidden_states(cfg: ModelConfig, params, tokens, states=None):
    b, s = tokens.shape
    emb0 = L.embed_tokens(cfg, params["embed"], tokens)
    x = emb0
    states = states if states is not None else init_mamba_states(cfg, b)
    x, new_states, _ = _run(cfg, params, x, emb0, states,
                            positions=jnp.arange(s))
    return L.rmsnorm(x, params["final_norm"]["scale"]), new_states


def loss_fn(cfg: ModelConfig, params, batch, rng=None):
    x, _ = hidden_states(cfg, params, batch["tokens"])
    loss = L.chunked_softmax_xent(cfg, params["embed"], x, batch["labels"],
                                  batch.get("mask"))
    return loss, {"loss": loss}


def logits_fn(cfg: ModelConfig, params, tokens):
    x, _ = hidden_states(cfg, params, tokens)
    return L.lm_head(cfg, params["embed"], x)


# ---------------------------------------------------------------------------
# inference
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int, dtype=None):
    dtype = dtype or cfg.jdtype
    napps = _n_apps(cfg)
    kv = (napps, batch_size, max_seq, cfg.kv_heads, cfg.head_dim)
    cache = init_mamba_states(cfg, batch_size)
    cache.update({"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                  "pos": jnp.zeros((batch_size,), jnp.int32)})
    return cache


def cache_axes(cfg: ModelConfig):
    return {"conv": ("layers", "batch", None, "mlp"),
            "ssd": ("layers", "batch", "heads", None, None),
            "k": (None, "batch", "kv_seq", "kv", None),
            "v": (None, "batch", "kv_seq", "kv", None),
            "pos": ("batch",)}


def prefill(cfg: ModelConfig, params, tokens, cache):
    b, s = tokens.shape
    emb0 = L.embed_tokens(cfg, params["embed"], tokens)
    states = {k: cache[k] for k in ("conv", "ssd")}
    x, new_states, shared_kvs = _run(cfg, params, emb0, emb0, states,
                                     positions=jnp.arange(s))
    new_cache = dict(new_states)
    max_seq = cache["k"].shape[2]
    ks = jnp.stack([kv[0] for kv in shared_kvs])           # [n_apps,B,S,Hkv,hd]
    vs = jnp.stack([kv[1] for kv in shared_kvs])
    new_cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    new_cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    new_cache["pos"] = jnp.full((b,), s, jnp.int32)
    x = L.rmsnorm(x, params["final_norm"]["scale"])
    return L.lm_head(cfg, params["embed"], x[:, -1:]), new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens):
    b = tokens.shape[0]
    pos = cache["pos"]
    emb0 = L.embed_tokens(cfg, params["embed"], tokens)
    states = {k: cache[k] for k in ("conv", "ssd")}
    shared = {"k": cache["k"], "v": cache["v"]}
    x, new_states, new_kvs = _run(cfg, params, emb0, emb0, states,
                                  positions=pos[:, None], shared_caches=shared,
                                  pos=pos, kv_valid_len=pos + 1)
    new_cache = dict(new_states)
    new_cache["k"] = jnp.stack([kv[0] for kv in new_kvs])
    new_cache["v"] = jnp.stack([kv[1] for kv in new_kvs])
    new_cache["pos"] = pos + 1
    x = L.rmsnorm(x, params["final_norm"]["scale"])
    return L.lm_head(cfg, params["embed"], x), new_cache


register_family("zamba2")(__import__("sys").modules[__name__])
