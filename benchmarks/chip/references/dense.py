"""Plain float32 reference of the dense decoder block, and the weights the
benchmark serves it with.

The block as the sources publish it (Hugging Face ``LlamaForCausalLM`` and
``Qwen2ForCausalLM``): RMSNorm, grouped-query attention with rotary position
embedding in rotate-half form (optional q/k/v biases), residual; RMSNorm,
SwiGLU MLP, residual; a final RMSNorm and an LM head tied to the embedding.
Straight ``jax.numpy`` in float32 at ``precision="highest"``: no kernels, no
cache, no batching.  Nothing here imports the program under test.

``make_weights`` lays the weights out as the dense family of ``repro.models``
takes them (per-layer leaves stacked on a leading axis), so that the same
function, from the same seed, feeds the program and this reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NORM_SCALE_STD = 0.1


def dims(conf: dict) -> dict:
    h = conf["num_attention_heads"]
    d = conf["hidden_size"]
    return {"L": conf["num_hidden_layers"], "d": d, "h": h,
            "hkv": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or d // h,
            "f": conf["intermediate_size"], "V": conf["vocab_size"],
            "bias": bool(conf.get("attention_bias", False))}


def program_config(conf: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for this
    configuration, as it is served."""
    n = dims(conf)
    if conf["hidden_act"] != "silu" or not conf["tie_word_embeddings"]:
        raise ValueError(f"{conf['name']}: the dense reference covers SwiGLU "
                         "blocks with a tied LM head only")
    return dict(name=conf["name"], family="dense", n_layers=n["L"],
                d_model=n["d"], n_heads=n["h"], n_kv_heads=n["hkv"],
                d_head=n["hd"], d_ff=n["f"], vocab_size=n["V"],
                qkv_bias=n["bias"], tie_embeddings=True,
                rope_theta=float(conf["rope_theta"]), attn_impl="blocked",
                dtype=conf["serve_dtype"])


def weight_shapes(conf: dict) -> dict:
    """{path: (shape, kind)}; kind is "matrix", "bias" or "norm"."""
    n = dims(conf)
    L, d, h, hkv, hd, f, V = (n[k] for k in ("L", "d", "h", "hkv", "hd",
                                             "f", "V"))
    s = {"embed/tok": ((V, d), "matrix"),
         "layers/ln1/scale": ((L, d), "norm"),
         "layers/attn/wq": ((L, d, h * hd), "matrix"),
         "layers/attn/wk": ((L, d, hkv * hd), "matrix"),
         "layers/attn/wv": ((L, d, hkv * hd), "matrix"),
         "layers/attn/wo": ((L, h * hd, d), "matrix"),
         "layers/ln2/scale": ((L, d), "norm"),
         "layers/mlp/wg": ((L, d, f), "matrix"),
         "layers/mlp/wu": ((L, d, f), "matrix"),
         "layers/mlp/wd": ((L, f, d), "matrix"),
         "final_norm/scale": ((d,), "norm")}
    if n["bias"]:
        for w, width in (("bq", h * hd), ("bk", hkv * hd), ("bv", hkv * hd)):
            s[f"layers/attn/{w}"] = ((L, width), "bias")
    return s


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    rest = seed >> 32
    while rest:
        key = jax.random.fold_in(key, rest & 0xFFFFFFFF)
        rest >>= 32
    return key


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


@functools.lru_cache(maxsize=None)
def _weights_fn(conf_json: str, dtype: str):
    import json
    conf = json.loads(conf_json)
    shapes = weight_shapes(conf)
    std = float(conf["initializer_range"])

    def make(key):
        flat = {}
        for i, (path, (shape, kind)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            w = 1.0 + NORM_SCALE_STD * z if kind == "norm" else std * z
            flat[path] = w.astype(dtype)
        return _nest(flat)

    return jax.jit(make)


def make_weights(conf: dict, seed: int, dtype: str):
    """Every weight of the configuration from ``seed``, made on the default
    device in one jitted call, in ``dtype``."""
    import json
    return _weights_fn(json.dumps(conf, sort_keys=True), dtype)(
        seed_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, D]: rotate-half rotary embedding over all D dims."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _dequant(w, mode):
    """Round a weight matrix [.., in, out] to ``mode`` with one scale per
    output column, and back to float32 (the precision control)."""
    if mode is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if mode in ("int8", "int4"):
        top = 127.0 if mode == "int8" else 7.0
        scale = jnp.maximum(amax, 1e-30) / top
        return jnp.clip(jnp.round(w / scale), -top, top) * scale
    if mode == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {mode!r}")


def logits(conf: dict, w: dict, tokens, quant=None):
    """tokens [S] int32 -> next-token logits [S, V] float32, causal.

    ``quant`` ("int8", "fp8" or "int4") rounds every weight matrix to that
    precision first (the embedding and tied LM head by row): the precision
    control.
    """
    n = dims(conf)
    h, hkv, hd = n["h"], n["hkv"], n["hd"]
    eps = float(conf["rms_norm_eps"])
    f32 = lambda a: a.astype(jnp.float32)
    q8 = lambda a: _dequant(f32(a), quant)
    tok_table = f32(w["embed"]["tok"])
    if quant is not None:
        tok_table = _dequant(tok_table.T, quant).T
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = tok_table[tokens]                                       # [S, d]
    mask = pos[:, None] >= pos[None, :]
    lay = w["layers"]

    def layer(x, p):
        a = _rmsnorm(x, f32(p["ln1"]["scale"]), eps)
        q = a @ q8(p["attn"]["wq"])
        k = a @ q8(p["attn"]["wk"])
        v = a @ q8(p["attn"]["wv"])
        if n["bias"]:
            q = q + f32(p["attn"]["bq"])
            k = k + f32(p["attn"]["bk"])
            v = v + f32(p["attn"]["bv"])
        q = _rope(q.reshape(S, h, hd), pos, float(conf["rope_theta"]))
        k = _rope(k.reshape(S, hkv, hd), pos, float(conf["rope_theta"]))
        v = v.reshape(S, hkv, hd)
        # query head i reads key/value head i // (h / hkv)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(S, h * hd) @ q8(p["attn"]["wo"])
        a = _rmsnorm(x, f32(p["ln2"]["scale"]), eps)
        m = jax.nn.silu(a @ q8(p["mlp"]["wg"])) * (a @ q8(p["mlp"]["wu"]))
        return x + m @ q8(p["mlp"]["wd"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, lay)
        x = _rmsnorm(x, f32(w["final_norm"]["scale"]), eps)
        return x @ tok_table.T


def matmul_params(conf: dict) -> int:
    """Weights that take part in a matrix product for each token, the tied
    LM head counted once (as the head) and norms and biases left out."""
    n = dims(conf)
    d, h, hkv, hd, f = n["d"], n["h"], n["hkv"], n["hd"], n["f"]
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f
    return n["L"] * per_layer + n["V"] * d


def attention_flops(conf: dict, context: int) -> int:
    """Q.K and P.V of one query token over ``context`` keys, all layers."""
    n = dims(conf)
    return 4 * n["L"] * n["h"] * n["hd"] * int(context)


def head_params(conf: dict) -> int:
    n = dims(conf)
    return n["V"] * n["d"]
