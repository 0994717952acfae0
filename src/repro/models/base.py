"""Model-zoo base: config dataclass, family registry, abstract-shape helpers.

Every architecture in the assigned pool is an instance of ``ModelConfig``
handled by one of the family modules (dense / moe / whisper / rwkv6 / zamba2 /
vlm).  The family module implements the functional model API:

    init(cfg, rng)                      -> params pytree
    loss_fn(cfg, params, batch, rng)    -> (loss, aux)          # training fwd
    prefill(cfg, params, batch)         -> (logits_last, cache) # inference
    decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
    init_cache(cfg, batch, seq)         -> cache pytree (abstract-safe)
    param_axes(cfg)                     -> logical-axis pytree (same structure
                                           as params; tuples of axis names)

Incremental single-sequence decode (optional; KV-cache-aware MCTS decode):

    prefill_fn(cfg, params, toks, plen) -> (logits, cache)
    step_fn(cfg, params, cache, tok, pos) -> (logits, cache)

Unlike ``prefill``/``decode_step`` these are *unbatched* (no leading batch
axis; ``tok``/``pos`` are scalars, ``logits`` is ``[V]`` fp32) so search
strategies can thread the cache through vmapped/scanned tree state
(``core.domains.lm_decode.CachedLMDecodeDomain``).  ``prefill_fn`` runs the
whole padded buffer ``toks`` once and returns the cache plus the next-token
logits at position ``plen - 1``; ``step_fn`` appends one token at ``pos``
and returns the logits for position ``pos + 1``.  Causality means cache
entries past the valid prefix are garbage-but-masked, never observed.
Families that do not implement the pair fall back to a pure-JAX generic
path (``seq_prefill``/``seq_step`` below) that recomputes the full forward
from a token-buffer "cache" — correct for every family, just uncached.

Params are plain nested dicts of jnp arrays; "stacked" per-layer weights carry
a leading ``layers`` logical axis and are consumed by ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

Params = Any
Pytree = Any


# families whose Pallas kernels (the rwkv6 and zamba2 scans) have no TPU
# compile guard in tests/test_tpu_compile.py: ``use_pallas=None`` keeps them
# on their jnp references
_PALLAS_UNGUARDED = ("rwkv6", "zamba2")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | whisper | rwkv6 | zamba2 | vlm
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab_size: int
    n_kv_heads: int = 0              # 0 -> = n_heads (MHA)
    d_head: int = 0                  # 0 -> d_model // n_heads

    # --- dense-family variants ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "silu"                # silu (SwiGLU) | gelu (plain MLP)
    qkv_bias: bool = False           # qwen2
    rope_frac: float = 1.0           # stablelm-2 partial rotary (0.25)
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    residual_scale: float = 1.0      # minicpm depth-scaled residuals
    logit_scale: float = 1.0         # minicpm mup output scaling

    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_topk: int = 0
    d_ff_expert: int = 0
    first_dense_layers: int = 0      # deepseek-v2: leading dense layers
    d_ff_dense: int = 0              # d_ff of those dense layers
    router_aux_coef: float = 0.001
    moe_capacity: float = 1.25       # dropped-token dispatch capacity factor
    moe_impl: str = "gather"         # gather (E,C buffers) | ragged (sort+ragged_dot)
    moe_groups: int = 1              # GShard grouped dispatch: groups shard over data
    scan_chunk: int = 64             # chunked-recurrence length (rwkv6 / ssd)
    logits_soft_cap: float = 0.0     # grok-1 tanh attention-logit cap

    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- rwkv6 ---
    rwkv_head_dim: int = 64
    rwkv_decay_lora: int = 64
    rwkv_mix_lora: int = 32

    # --- zamba2 / mamba2 ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    shared_attn_every: int = 6       # apply shared attention block every N ssm blocks

    # --- encoder-decoder (whisper) ---
    n_enc_layers: int = 0
    enc_seq: int = 1500              # stub frame-embedding length

    # --- modality stubs ---
    n_patches: int = 0               # vlm: stub patch embeddings prepended
    frontend_dim: int = 0            # dim of stub embeddings (== d_model here)

    # --- numerics / compile strategy ---
    attn_impl: str = "sdpa"          # sdpa (materialized) | blocked (online softmax)
    seq_shard_carry: bool = False    # Megatron-SP: layer-boundary activations
                                     # (scan-saved carries) sharded over model
    attn_blk_q: int = 256
    attn_blk_k: int = 1024
    dtype: str = "bfloat16"
    remat: bool = True
    use_scan: bool = True
    ce_chunk: int = 512              # chunked cross-entropy block (tokens)
    # Pallas kernels on the model's inference path; read through ``pallas``
    use_pallas: Optional[bool] = None
    max_seq: int = 8192              # rope table default cap (runtime extends)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def pallas(self) -> bool:
        """``use_pallas`` resolved, the one rule every family reads: True or
        False forces a side (e.g. for parity); None follows the platform
        (Pallas on TPU, the jnp references elsewhere) except for the
        families whose kernels have no TPU compile guard, which keep their
        references."""
        if self.use_pallas is None:
            return (jax.default_backend() == "tpu"
                    and self.family not in _PALLAS_UNGUARDED)
        return self.use_pallas

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


# ---------------------------------------------------------------------------
# family registry
# ---------------------------------------------------------------------------
_FAMILIES: Dict[str, Any] = {}


def register_family(name: str):
    def deco(mod):
        _FAMILIES[name] = mod
        return mod
    return deco


_FAMILY_MODULES = {
    "dense": "transformer",
    "moe": "moe",
    "whisper": "whisper",
    "rwkv6": "rwkv6",
    "zamba2": "zamba2",
    "vlm": "vlm",
}


def get_family(cfg_or_name):
    name = cfg_or_name.family if isinstance(cfg_or_name, ModelConfig) else cfg_or_name
    if name not in _FAMILIES:
        # import side-effect registration
        import importlib
        importlib.import_module(f"repro.models.{_FAMILY_MODULES.get(name, name)}")
    return _FAMILIES[name]


# ---------------------------------------------------------------------------
# incremental single-sequence decode (unbatched; see module docstring)
# ---------------------------------------------------------------------------
def _generic_prefill(cfg: ModelConfig, params, toks, plen):
    """Fallback prefill: the "cache" is just the token buffer itself."""
    fam = get_family(cfg)
    logits = fam.logits_fn(cfg, params, toks[None])[0]
    last = jax.lax.dynamic_index_in_dim(
        logits, jnp.asarray(plen, jnp.int32) - 1, axis=0, keepdims=False)
    return last.astype(jnp.float32), {"toks": toks.astype(jnp.int32)}


def _generic_step(cfg: ModelConfig, params, cache, tok, pos):
    """Fallback step: write ``tok`` at ``pos`` and re-run the full forward.

    Functionally identical to the cached path (same logits), with no
    compute amortization — the contract the parity tests pin down.
    """
    fam = get_family(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    toks = cache["toks"].at[pos].set(jnp.asarray(tok, jnp.int32), mode="drop")
    logits = fam.logits_fn(cfg, params, toks[None])[0]
    out = jax.lax.dynamic_index_in_dim(logits, pos, axis=0, keepdims=False)
    return out.astype(jnp.float32), {"toks": toks}


def seq_prefill(cfg: ModelConfig, params, toks, plen):
    """Single-sequence prefill: ``toks [S] i32`` (padded buffer), ``plen``
    scalar true length -> ``(logits [V] f32 at plen-1, cache)``.  Dispatches
    to the family's ``prefill_fn`` when present, else the generic fallback.
    """
    fam = get_family(cfg)
    fn = getattr(fam, "prefill_fn", None)
    if fn is None:
        return _generic_prefill(cfg, params, toks, plen)
    return fn(cfg, params, toks, plen)


def seq_step(cfg: ModelConfig, params, cache, tok, pos):
    """Single-sequence incremental step: append ``tok`` (scalar i32) at
    ``pos`` -> ``(logits [V] f32 for pos+1, cache)``.  ``cache`` must come
    from ``seq_prefill`` (or a prior ``seq_step``) with the same cfg/params.
    """
    fam = get_family(cfg)
    fn = getattr(fam, "step_fn", None)
    if fn is None:
        return _generic_step(cfg, params, cache, tok, pos)
    return fn(cfg, params, cache, tok, pos)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig) -> Pytree:
    """ShapeDtypeStruct pytree of the params — no allocation (dry-run path)."""
    fam = get_family(cfg)
    return jax.eval_shape(lambda k: fam.init(cfg, k), jax.random.key(0))


def count_params(tree: Pytree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def active_param_count(cfg: ModelConfig) -> int:
    """Activated parameters per token (MoE discounts inactive experts)."""
    total = count_params(abstract_params(cfg))
    if cfg.n_experts and cfg.moe_topk:
        fam = get_family(cfg)
        if hasattr(fam, "inactive_expert_params"):
            total -= fam.inactive_expert_params(cfg)
    return total
