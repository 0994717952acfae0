"""RWKV-6 "Finch" (arXiv:2404.05892) — attention-free RNN LM.

Block = TimeMix (WKV6 recurrence, data-dependent per-channel decay via LoRA)
      + ChannelMix (squared-ReLU FFN with token-shift).

State per layer: WKV state [B, H, N, N] + two token-shift slots [B, D]
(time-mix and channel-mix).  Decode is O(1) in context length — the
``long_500k`` cell runs with constant memory/compute per token.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_scan import ops as wkv_ops
from repro.models import layers as L
from repro.models.base import ModelConfig, register_family


def _heads(cfg: ModelConfig):
    n = cfg.rwkv_head_dim
    return cfg.d_model // n, n


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_block(cfg: ModelConfig, key):
    d = cfg.d_model
    h, n = _heads(cfg)
    lm, ld = cfg.rwkv_mix_lora, cfg.rwkv_decay_lora
    ks = jax.random.split(key, 12)
    dt = cfg.jdtype
    tm = {
        "maa_x": jnp.zeros((d,), dt),
        "maa_rkvwg": jnp.zeros((5, d), dt),
        "maa_w1": L.dense_init(ks[0], (d, 5 * lm), dt),
        "maa_w2": L.dense_init(ks[1], (5, lm, d), dt, in_axis=1),
        "decay": jnp.full((d,), -6.0, dt),
        "decay_w1": L.dense_init(ks[2], (d, ld), dt),
        "decay_w2": L.dense_init(ks[3], (ld, d), dt),
        "faaaa": jnp.full((h, n), 0.5, dt),
        "wr": L.dense_init(ks[4], (d, d), dt),
        "wk": L.dense_init(ks[5], (d, d), dt),
        "wv": L.dense_init(ks[6], (d, d), dt),
        "wg": L.dense_init(ks[7], (d, d), dt),
        "wo": L.dense_init(ks[8], (d, d), dt),
        "ln_x_scale": jnp.ones((d,), dt),
        "ln_x_bias": jnp.zeros((d,), dt),
    }
    cm = {
        "maa_k": jnp.zeros((d,), dt),
        "maa_r": jnp.zeros((d,), dt),
        "wk": L.dense_init(ks[9], (d, cfg.d_ff), dt),
        "wv": L.dense_init(ks[10], (cfg.d_ff, d), dt),
        "wr": L.dense_init(ks[11], (d, d), dt),
    }
    ln = {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    return {"ln1": dict(ln), "time_mix": tm, "ln2": dict(ln), "channel_mix": cm}


def init(cfg: ModelConfig, key):
    k_emb, k_layers, k_f = jax.random.split(key, 3)
    stacked = jax.vmap(lambda k: _init_block(cfg, k))(jax.random.split(k_layers, cfg.n_layers))
    d = cfg.d_model
    return {
        "embed": L.init_embed(cfg, k_emb),
        "ln0": {"scale": jnp.ones((d,), cfg.jdtype), "bias": jnp.zeros((d,), cfg.jdtype)},
        "layers": stacked,
        "final_norm": {"scale": jnp.ones((d,), cfg.jdtype), "bias": jnp.zeros((d,), cfg.jdtype)},
    }


def param_axes(cfg: ModelConfig):
    ln = {"scale": (None,), "bias": (None,)}
    tm = {"maa_x": (None,), "maa_rkvwg": (None, None),
          "maa_w1": ("embed", None), "maa_w2": (None, None, "embed"),
          "decay": (None,), "decay_w1": ("embed", None), "decay_w2": (None, "embed"),
          "faaaa": ("heads", None),
          "wr": ("embed", "heads"), "wk": ("embed", "heads"),
          "wv": ("embed", "heads"), "wg": ("embed", "heads"),
          "wo": ("heads", "embed"), "ln_x_scale": (None,), "ln_x_bias": (None,)}
    cm = {"maa_k": (None,), "maa_r": (None,), "wk": ("embed", "mlp"),
          "wv": ("mlp", "embed"), "wr": ("embed", "heads")}
    blk = {"ln1": dict(ln), "time_mix": tm, "ln2": dict(ln), "channel_mix": cm}
    stack = jax.tree_util.tree_map(lambda ax: ("layers",) + ax, blk,
                                   is_leaf=lambda x: isinstance(x, tuple))
    emb = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        emb["head"] = ("embed", "vocab")
    return {"embed": emb, "ln0": dict(ln), "layers": stack, "final_norm": dict(ln)}


# ---------------------------------------------------------------------------
# block forward (sequence mode: token shift via roll; state mode for decode)
# ---------------------------------------------------------------------------
def _ddlerp(p, x, x_prev):
    """Data-dependent lerp producing the 5 mixed inputs (r,k,v,w,g)."""
    xx = x_prev - x
    xxx = x + xx * p["maa_x"]
    b, s, d = x.shape
    lo = jnp.tanh(xxx @ p["maa_w1"]).reshape(b, s, 5, -1)         # [B,S,5,lm]
    mods = jnp.einsum("bsfl,fld->fbsd", lo, p["maa_w2"])          # [5,B,S,D]
    mix = p["maa_rkvwg"][:, None, None, :] + mods
    return x[None] + xx[None] * mix                                # [5,B,S,D]


def _time_mix(cfg: ModelConfig, p, x, x_prev, wkv_state, *, use_pallas=False):
    """x [B,S,D]; x_prev [B,S,D] (token-shifted); wkv_state [B,H,N,N]."""
    b, s, d = x.shape
    h, n = _heads(cfg)
    xr, xk, xv, xw, xg = _ddlerp(p, x, x_prev)
    r = (xr @ p["wr"]).reshape(b, s, h, n)
    k = (xk @ p["wk"]).reshape(b, s, h, n)
    v = (xv @ p["wv"]).reshape(b, s, h, n)
    g = jax.nn.silu((xg @ p["wg"]).astype(jnp.float32)).astype(x.dtype)
    w_raw = p["decay"].astype(jnp.float32) + \
        (jnp.tanh(xw @ p["decay_w1"]) @ p["decay_w2"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(w_raw)).reshape(b, s, h, n)               # decay in (0,1)
    u = p["faaaa"]
    y, new_state = wkv_ops.wkv6(r, k, v, w, u, wkv_state, use_pallas=use_pallas)
    # per-head groupnorm
    y32 = y.astype(jnp.float32).reshape(b, s, h, n)
    mu = y32.mean(-1, keepdims=True)
    var = y32.var(-1, keepdims=True)
    y32 = (y32 - mu) * jax.lax.rsqrt(var + 1e-5)
    y = (y32.reshape(b, s, d) * p["ln_x_scale"].astype(jnp.float32)
         + p["ln_x_bias"].astype(jnp.float32)).astype(x.dtype)
    return (y * g) @ p["wo"], new_state


def _channel_mix(cfg: ModelConfig, p, x, x_prev):
    xx = x_prev - x
    xk = x + xx * p["maa_k"]
    xr = x + xx * p["maa_r"]
    k = jnp.square(jax.nn.relu((xk @ p["wk"]).astype(jnp.float32))).astype(x.dtype)
    return jax.nn.sigmoid((xr @ p["wr"]).astype(jnp.float32)).astype(x.dtype) * (k @ p["wv"])


def _shift_seq(x, first):
    """Token shift: x_prev[t] = x[t-1]; x_prev[0] = first (carried state)."""
    return jnp.concatenate([first[:, None], x[:, :-1]], axis=1)


def _block_seq(cfg: ModelConfig, lp, x, state):
    """Full-sequence block. state = {wkv, tm_prev [B,D], cm_prev [B,D]}."""
    from repro.parallel.sharding import with_logical_constraint
    x = with_logical_constraint(x, ("batch", None, None))
    h1 = L.layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
    prev = _shift_seq(h1, state["tm_prev"])
    out, wkv = _time_mix(cfg, lp["time_mix"], h1, prev, state["wkv"],
                         use_pallas=cfg.pallas)
    x = x + out
    h2 = L.layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    prev2 = _shift_seq(h2, state["cm_prev"])
    x = x + _channel_mix(cfg, lp["channel_mix"], h2, prev2)
    new_state = {"wkv": wkv, "tm_prev": h1[:, -1], "cm_prev": h2[:, -1]}
    return x, new_state


def init_state(cfg: ModelConfig, batch_size: int, dtype=None):
    h, n = _heads(cfg)
    d = cfg.d_model
    return {
        "wkv": jnp.zeros((cfg.n_layers, batch_size, h, n, n), jnp.float32),
        "tm_prev": jnp.zeros((cfg.n_layers, batch_size, d), cfg.jdtype),
        "cm_prev": jnp.zeros((cfg.n_layers, batch_size, d), cfg.jdtype),
        "pos": jnp.zeros((batch_size,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig):
    return {"wkv": ("layers", "batch", "heads", None, None),
            "tm_prev": ("layers", "batch", None),
            "cm_prev": ("layers", "batch", None),
            "pos": ("batch",)}


init_cache = lambda cfg, batch_size, max_seq, dtype=None: init_state(cfg, batch_size, dtype)


def _run(cfg: ModelConfig, params, x, state):
    def body(carry, xs):
        x = carry
        lp, st = xs
        x, new_st = _block_seq(cfg, lp, x, st)
        if cfg.seq_shard_carry and x.shape[1] > 1:
            from repro.parallel.sharding import with_logical_constraint
            x = with_logical_constraint(x, ("batch", "act_seq", None))
        return x, new_st

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    layer_states = {k: state[k] for k in ("wkv", "tm_prev", "cm_prev")}
    x, new_states = jax.lax.scan(body, x, (params["layers"], layer_states))
    return x, new_states


def hidden_states(cfg: ModelConfig, params, tokens, state=None):
    b = tokens.shape[0]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    x = L.layernorm(x, params["ln0"]["scale"], params["ln0"]["bias"])
    state = state or init_state(cfg, b)
    x, new_states = _run(cfg, params, x, state)
    return L.layernorm(x, params["final_norm"]["scale"], params["final_norm"]["bias"]), new_states


def loss_fn(cfg: ModelConfig, params, batch, rng=None):
    x, _ = hidden_states(cfg, params, batch["tokens"])
    loss = L.chunked_softmax_xent(cfg, params["embed"], x, batch["labels"],
                                  batch.get("mask"))
    return loss, {"loss": loss}


def logits_fn(cfg: ModelConfig, params, tokens):
    x, _ = hidden_states(cfg, params, tokens)
    return L.lm_head(cfg, params["embed"], x)


def prefill(cfg: ModelConfig, params, tokens, cache):
    b, s = tokens.shape
    x, new_states = hidden_states(cfg, params, tokens)
    new_cache = dict(new_states)
    new_cache["pos"] = jnp.full((b,), s, jnp.int32)
    return L.lm_head(cfg, params["embed"], x[:, -1:]), new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """tokens [B,1] -> (logits, state). O(1) per token."""
    b = tokens.shape[0]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    x = L.layernorm(x, params["ln0"]["scale"], params["ln0"]["bias"])
    state = {k: cache[k] for k in ("wkv", "tm_prev", "cm_prev")}
    x, new_states = _run(cfg, params, x, state)
    x = L.layernorm(x, params["final_norm"]["scale"], params["final_norm"]["bias"])
    out = dict(new_states)
    out["pos"] = cache["pos"] + 1
    return L.lm_head(cfg, params["embed"], x), out


register_family("rwkv6")(__import__("sys").modules[__name__])
