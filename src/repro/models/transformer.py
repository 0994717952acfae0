"""Dense decoder-only transformer family.

Covers smollm-135m, qwen2-0.5b, minicpm-2b, stablelm-3b and the internlm2
backbone of internvl2-2b via ModelConfig flags (norm type, partial rotary,
qkv bias, residual/logit scaling, GQA widths).

Layers are stacked on a leading axis and executed with ``lax.scan`` so compile
time is depth-independent; each block is rematerialized when ``cfg.remat``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.base import ModelConfig, register_family


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_block(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)
    return {
        "ln1": L.init_norm(cfg, ks[0]),
        "attn": L.init_gqa(cfg, ks[1]),
        "ln2": L.init_norm(cfg, ks[2]),
        "mlp": L.init_mlp(cfg, ks[3]),
    }


def init(cfg: ModelConfig, key):
    k_emb, k_layers, k_final = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: _init_block(cfg, k))(layer_keys)
    return {
        "embed": L.init_embed(cfg, k_emb),
        "layers": stacked,
        "final_norm": L.init_norm(cfg, k_final),
    }


def param_axes(cfg: ModelConfig):
    """Logical-axis names, same tree structure as init()."""
    def blk():
        attn = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
                "wv": ("embed", "kv"), "wo": ("heads", "embed")}
        if cfg.qkv_bias:
            attn.update({"bq": ("heads",), "bk": ("kv",), "bv": ("kv",)})
        mlp = ({"wi": ("embed", "mlp"), "bi": ("mlp",),
                "wo": ("mlp", "embed"), "bo": ("embed",)}
               if cfg.act == "gelu" else
               {"wg": ("embed", "mlp"), "wu": ("embed", "mlp"), "wd": ("mlp", "embed")})
        norm = ({"scale": (None,), "bias": (None,)} if cfg.norm == "layernorm"
                else {"scale": (None,)})
        return {"ln1": dict(norm), "attn": attn, "ln2": dict(norm), "mlp": mlp}

    def stack(tree):
        return jax.tree_util.tree_map(lambda ax: ("layers",) + ax, tree,
                                      is_leaf=lambda x: isinstance(x, tuple))

    emb = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        emb["head"] = ("embed", "vocab")
    norm = ({"scale": (None,), "bias": (None,)} if cfg.norm == "layernorm"
            else {"scale": (None,)})
    return {"embed": emb, "layers": stack(blk()), "final_norm": dict(norm)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _block(cfg: ModelConfig, p, x, cos, sin, *, causal=True, q_offset=0,
           cache_kv=None, pos=None, kv_valid_len=None):
    """One transformer block. Returns (x, new_cache_kv or None)."""
    from repro.parallel.sharding import with_logical_constraint
    x = with_logical_constraint(x, ("batch", None, None))
    h = L.apply_norm(cfg, p["ln1"], x)
    q, k, v = L.gqa_project_qkv(cfg, p["attn"], h)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    new_kv = None
    if cache_kv is not None:
        ck, cv = cache_kv
        b = x.shape[0]
        ck = ck.at[jnp.arange(b), pos].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[jnp.arange(b), pos].set(v[:, 0].astype(cv.dtype))
        k, v, new_kv = ck, cv, (ck, cv)
        attn_out = L.attention(cfg, q, k, v, causal=False,
                               kv_valid_len=kv_valid_len, kernels=True)
    else:
        attn_out = L.attention(cfg, q, k, v, causal=causal, q_offset=q_offset)
    b, s = x.shape[:2]
    x = x + (attn_out.reshape(b, s, -1) @ p["attn"]["wo"]) * cfg.residual_scale
    h = L.apply_norm(cfg, p["ln2"], x)
    x = x + L.apply_mlp(cfg, p["mlp"], h) * cfg.residual_scale
    return x, new_kv


def _run_stack(cfg: ModelConfig, params, x, cos, sin, *, q_offset=0):
    """scan over stacked layers (training / prefill: no cache)."""
    def body(carry, lp):
        y, _ = _block(cfg, lp, carry, cos, sin, causal=True, q_offset=q_offset)
        if cfg.seq_shard_carry:
            from repro.parallel.sharding import with_logical_constraint
            y = with_logical_constraint(y, ("batch", "act_seq", None))
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.use_scan:
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, _ = body(x, lp)
    return x


def hidden_states(cfg: ModelConfig, params, tokens=None, inputs_embeds=None, positions=None):
    """Full-sequence forward to final hidden states [B,S,D]."""
    x = inputs_embeds if inputs_embeds is not None else L.embed_tokens(cfg, params["embed"], tokens)
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s)
    cos, sin = L.rope_freqs(cfg, positions)
    x = _run_stack(cfg, params, x, cos, sin)
    return L.apply_norm(cfg, params["final_norm"], x)


def loss_fn(cfg: ModelConfig, params, batch, rng=None):
    x = hidden_states(cfg, params, tokens=batch["tokens"])
    loss = L.chunked_softmax_xent(cfg, params["embed"], x, batch["labels"],
                                  batch.get("mask"))
    return loss, {"loss": loss}


def logits_fn(cfg: ModelConfig, params, tokens):
    x = hidden_states(cfg, params, tokens=tokens)
    return L.lm_head(cfg, params["embed"], x)


# ---------------------------------------------------------------------------
# inference: prefill + single-token decode with pre-allocated KV cache
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int, dtype=None):
    dtype = dtype or cfg.jdtype
    kv_shape = (cfg.n_layers, batch_size, max_seq, cfg.kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(kv_shape, dtype),
        "v": jnp.zeros(kv_shape, dtype),
        "pos": jnp.zeros((batch_size,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig):
    return {"k": ("layers", "batch", "kv_seq", "kv", None),
            "v": ("layers", "batch", "kv_seq", "kv", None),
            "pos": ("batch",)}


def _prefill_stack(cfg: ModelConfig, params, tokens):
    """Shared prompt pass: tokens [B,S] -> (final-normed hidden [B,S,D],
    per-layer ks, vs [L,B,S,Hkv,D]).  Backs both the batched ``prefill`` and
    the unbatched ``prefill_fn`` so the block arithmetic exists once."""
    b, s = tokens.shape
    x = L.embed_tokens(cfg, params["embed"], tokens)
    cos, sin = L.rope_freqs(cfg, jnp.arange(s))

    def body(carry, lp):
        y = carry
        h = L.apply_norm(cfg, lp["ln1"], y)
        q, k, v = L.gqa_project_qkv(cfg, lp["attn"], h)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        attn_out = L.attention(cfg, q, k, v, causal=True, kernels=True)
        y = y + (attn_out.reshape(b, s, -1) @ lp["attn"]["wo"]) * cfg.residual_scale
        h = L.apply_norm(cfg, lp["ln2"], y)
        y = y + L.apply_mlp(cfg, lp["mlp"], h) * cfg.residual_scale
        return y, (k, v)

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    return L.apply_norm(cfg, params["final_norm"], x), ks, vs


def prefill(cfg: ModelConfig, params, tokens, cache):
    """Run the prompt, fill the cache, return last-position logits."""
    b, s = tokens.shape
    x, ks, vs = _prefill_stack(cfg, params, tokens)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["pos"] = jnp.full((b,), s, jnp.int32)
    return L.lm_head(cfg, params["embed"], x[:, -1:]), cache


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """tokens [B,1] -> (logits [B,1,V], cache). Positions come from cache."""
    b = tokens.shape[0]
    pos = cache["pos"]                      # [B]
    x = L.embed_tokens(cfg, params["embed"], tokens)
    cos, sin = L.rope_freqs(cfg, pos[:, None])
    valid = pos + 1

    def body(carry, xs):
        y = carry
        lp, ck, cv = xs
        y, new_kv = _block(cfg, lp, y, cos, sin, cache_kv=(ck, cv), pos=pos,
                           kv_valid_len=valid)
        return y, new_kv

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    cache = dict(cache)
    cache["k"], cache["v"] = ks, vs
    cache["pos"] = pos + 1
    x = L.apply_norm(cfg, params["final_norm"], x)
    return L.lm_head(cfg, params["embed"], x), cache


# ---------------------------------------------------------------------------
# incremental single-sequence decode (unbatched; base.seq_prefill/seq_step)
# ---------------------------------------------------------------------------
def prefill_fn(cfg: ModelConfig, params, toks, plen):
    """toks [S] i32 padded buffer, plen scalar true length ->
    (logits [V] f32 at position plen-1, cache {k, v: [L, S, Hkv, D]}).

    Runs the whole buffer once (causal), so cache rows at positions >= plen
    hold K/V of padding tokens — masked by ``step_fn``'s valid length and
    overwritten as the sequence grows, never observed.
    """
    x, ks, vs = _prefill_stack(cfg, params, toks[None])
    h_last = jax.lax.dynamic_index_in_dim(
        x[0], jnp.asarray(plen, jnp.int32) - 1, axis=0, keepdims=False)
    logits = L.lm_head(cfg, params["embed"], h_last[None, None])[0, 0]
    return logits.astype(jnp.float32), {"k": ks[:, 0], "v": vs[:, 0]}


def step_fn(cfg: ModelConfig, params, cache, tok, pos):
    """One incremental token: cache {k, v: [L, S, Hkv, D]}, tok/pos scalars
    -> (logits [V] f32 for position pos+1, cache).  Attention reads the
    per-layer cache row through ``kernels/decode_attention`` (Pallas when
    ``cfg.pallas``, i.e. on TPU by default; the jnp oracle elsewhere).

    Kept as its own scan body rather than reusing ``_block``: the cache here
    is unbatched (rows are vmapped by the search strategies), and the
    attention is pinned to the decode kernel's flash path instead of
    ``L.attention``'s sdpa-with-bias dispatch.
    """
    from repro.kernels.decode_attention import ops as da

    pos = jnp.asarray(pos, jnp.int32)
    x = L.embed_tokens(cfg, params["embed"],
                       jnp.asarray(tok, jnp.int32).reshape(1, 1))
    cos, sin = L.rope_freqs(cfg, pos.reshape(1, 1))
    valid = (pos + 1).reshape(1)

    def body(carry, xs):
        y = carry
        lp, ck, cv = xs                              # ck/cv [S, Hkv, D]
        h = L.apply_norm(cfg, lp["ln1"], y)
        q, k, v = L.gqa_project_qkv(cfg, lp["attn"], h)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        ck = jax.lax.dynamic_update_slice(ck, k[0].astype(ck.dtype), (pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v[0].astype(cv.dtype), (pos, 0, 0))
        attn_out = da.decode_attention(q, ck[None], cv[None], valid,
                                       use_ref=not cfg.pallas)
        y = y + (attn_out.reshape(1, 1, -1) @ lp["attn"]["wo"]) * cfg.residual_scale
        h = L.apply_norm(cfg, lp["ln2"], y)
        y = y + L.apply_mlp(cfg, lp["mlp"], h) * cfg.residual_scale
        return y, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_head(cfg, params["embed"], x)[0, 0]
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


register_family("dense")(__import__("sys").modules[__name__])
