"""UCT / PUCT child scoring — reference jnp path + Pallas-kernel dispatch.

Paper eq. (1):  UCT_j = w_j / n_j + C_p * sqrt(ln(n) / n_j)

Two in-flight decorrelation modes (``vl_mode``, DESIGN.md §15):

``"loss"`` — classic virtual loss (§IV related work / DESIGN §2):
    n_j^eff = n_j + vl_j
    w_j^eff = w_j - vl_weight * vl_j     (pessimistic in-flight estimate)
Q is *corrupted* while playouts are in flight — the price of the simple
single-plane bookkeeping.

``"wu"`` — WU-UCT (arXiv 1810.11755): track initiated-but-incomplete
playouts as an unobserved-sample count O_j that widens only exploration:
    Q_j       = w_j / max(n_j, 1)                      (completed stats only)
    explore_j = sqrt(ln(n_p + O_p) / max(n_j + O_j, 1))
Q is bit-identical whether 0 or 1000 playouts are in flight through j.

Must-explore ordering (intended, both modes, ref == Pallas bit-for-bit):
an *idle* unvisited child (effective count < 0.5 — loss: N+vl, wu: N+O)
gets the ``1e30`` sentinel and always wins; an *in-flight* unvisited child
scores finitely (loss: ``-vl_weight + cp*explore``; wu: ``0 + cp*explore``)
so lanes spread over idle siblings first.  Sentinel ties resolve to the
lowest index — both the jnp path and the kernel use first-max ``argmax``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-1e30)

VL_MODES = ("loss", "wu")


def uct_scores(child_n, child_w, child_vl, parent_n, cp, *, vl_weight=1.0,
               prior=None, puct=False, child_o=None, vl_mode="loss"):
    """All inputs per-child [..., A]; parent_n broadcastable. fp32 scores.

    ``vl_mode="loss"`` reads ``child_vl`` and ignores ``child_o``;
    ``"wu"`` reads ``child_o`` and ignores ``child_vl``.  ``parent_n`` must
    already include the same mode's in-flight count (N_p + vl_p or N_p + O_p
    — callers own that sum so lockstep can exclude a lane's own count).
    """
    if vl_mode not in VL_MODES:
        raise ValueError(f"vl_mode must be one of {VL_MODES}, got {vl_mode!r}")
    n = child_n.astype(jnp.float32)
    pn = jnp.maximum(parent_n.astype(jnp.float32), 1.0)
    if vl_mode == "wu":
        o = jnp.zeros_like(n) if child_o is None \
            else child_o.astype(jnp.float32)
        n_eff = n + o                       # widens exploration only
        q = child_w / jnp.maximum(n, 1.0)   # completed statistics only
    else:
        vl = child_vl.astype(jnp.float32)
        n_eff = n + vl
        q = (child_w - vl_weight * vl) / jnp.maximum(n_eff, 1.0)
    if puct:
        assert prior is not None
        explore = prior * jnp.sqrt(pn)[..., None] / (1.0 + n_eff)
    else:
        explore = jnp.sqrt(jnp.log(pn)[..., None] / jnp.maximum(n_eff, 1.0))
    scores = q + cp * explore
    # unvisited & not in flight -> must-explore (paper: UCT = inf)
    return jnp.where(n_eff < 0.5, jnp.float32(1e30), scores)


def _check_kernel(use_pallas, puct):
    if use_pallas and puct:
        raise ValueError("the Pallas UCT kernel takes no priors and cannot "
                         "score PUCT; pass use_pallas=False (see "
                         "SearchParams.resolved_uct_kernels)")


def uct_argmax(child_n, child_w, child_vl, parent_n, cp, *, vl_weight=1.0,
               prior=None, puct=False, valid=None, use_pallas=False,
               interpret=False, child_o=None, vl_mode="loss"):
    """Best child index along the last axis. ``valid`` masks illegal slots.
    The Pallas kernel takes no priors: ``use_pallas`` with ``puct`` raises."""
    _check_kernel(use_pallas, puct)
    if use_pallas:
        from repro.kernels.uct_select import ops as uops
        return uops.uct_argmax(child_n, child_w, child_vl, parent_n,
                               cp=cp, vl_weight=vl_weight,
                               valid=valid, interpret=interpret,
                               child_o=child_o, vl_mode=vl_mode)
    s = uct_scores(child_n, child_w, child_vl, parent_n, cp,
                   vl_weight=vl_weight, prior=prior, puct=puct,
                   child_o=child_o, vl_mode=vl_mode)
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    return jnp.argmax(s, axis=-1).astype(jnp.int32)


def uct_argmax_running(child_n, child_w, child_vl, parent_n, parent_id, cp, *,
                       vl_weight=1.0, prior=None, puct=False, valid=None,
                       use_pallas=False, interpret=False, child_o=None,
                       vl_mode="loss"):
    """Running-assignment argmax over one wave's ``[lanes, A]`` level board
    (DESIGN.md §16): lanes are assigned IN ORDER, and lane k scores with the
    in-flight counts already incremented by the picks of lanes ``0..k-1``
    that share k's parent (``parent_id``, the node whose children row lane k
    is scoring) at this same level.  One call still serves the whole wave —
    the Pallas path is a single launch with a sequential row walk — but
    co-located lanes spread over viable children instead of stacking.

    The running delta joins the mode's in-flight plane before the shared
    scoring formula: in "loss" mode it rides ``child_vl`` (affecting both Q
    and the effective count), in "wu" mode it rides ``child_o`` (widening
    exploration only).  ``parent_n`` is NOT adjusted — earlier lanes'
    presence at the parent is already counted by the caller's per-level
    plane.  A lane whose ``valid`` row is all-False contributes nothing and
    returns index 0.  At ``lanes == 1`` the delta is identically zero, so
    the result is bit-for-bit equal to ``uct_argmax``.
    """
    lanes, a = child_n.shape
    if valid is None:
        valid = jnp.ones((lanes, a), bool)
    _check_kernel(use_pallas, puct)
    if use_pallas:
        from repro.kernels.uct_select import ops as uops
        return uops.uct_argmax_running(
            child_n, child_w, child_vl, parent_n, parent_id,
            cp=cp, vl_weight=vl_weight, valid=valid, interpret=interpret,
            child_o=child_o, vl_mode=vl_mode)
    if child_o is None:
        child_o = jnp.zeros((lanes, a), jnp.int32)
    active = valid.any(axis=-1)                            # [lanes]
    same = parent_id[:, None] == parent_id[None, :]        # [lanes, lanes]
    iota_a = jnp.arange(a)

    def body(contrib, k):
        # contrib[m]: same-parent picks of lanes < k, keyed on lane m's slots
        d = contrib[k]
        if vl_mode == "wu":
            vl_k, o_k = child_vl[k], child_o[k] + d
        else:
            vl_k, o_k = child_vl[k] + d, child_o[k]
        pn_k = parent_n[k] if jnp.ndim(parent_n) else parent_n
        s = uct_scores(child_n[k], child_w[k], vl_k, pn_k, cp,
                       vl_weight=vl_weight,
                       prior=None if prior is None else prior[k],
                       puct=puct, child_o=o_k, vl_mode=vl_mode)
        s = jnp.where(valid[k], s, NEG_INF)
        pick = jnp.argmax(s).astype(jnp.int32)
        add = ((iota_a == pick) & active[k]).astype(contrib.dtype)
        contrib = contrib + jnp.where(
            (same[:, k] & active[k])[:, None], add[None, :], 0)
        return contrib, pick

    _, picks = jax.lax.scan(
        body, jnp.zeros((lanes, a), jnp.float32), jnp.arange(lanes))
    return picks
