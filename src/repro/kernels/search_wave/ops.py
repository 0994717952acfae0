"""Fused search-wave entry points: ref / Pallas dispatch + arena plumbing.

``core.stages.mega_round`` / ``mega_tick`` land here.  The implementation
is chosen by ``SearchParams.kernels`` ("pallas" on TPU under "auto"),
overridable per-call for tests (``impl=``, ``interpret=`` to run the
Pallas kernels on CPU via the interpreter).

This module owns the arena <-> kernel-plane packing:

* 1-D arena planes (visits/value/in-flight/terminal/free_list) ride as
  ``[N, 1]`` VMEM blocks; 2-D planes (children/prior) as ``[N, A]``.  The
  in-flight slot carries ``tree.vloss`` ("loss" mode) or ``tree.unobs``
  ("wu" mode, WU-UCT O counts) — see ``kernel.WaveCfg``;
* ``next_free`` / ``free_top`` / wave validity ride in one ``[1, 4]``
  scalar word;
* the kernel mutates visits/value/vloss/prior/children in place
  (input/output aliased) and emits the Select buffers + structural Expand
  result; parent/action pointers, the free-list bookkeeping, and the path
  append are cheap scatter/where updates applied here, outside the launch
  (they are not on the per-level critical path the fusion removes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core.arena import UNEXPANDED, TreeArena
from repro.kernels.search_wave import kernel as K
from repro.kernels.search_wave import ref


def _cfg(tree: TreeArena, sp, lanes: int) -> K.WaveCfg:
    return K.WaveCfg(n=tree.max_nodes, a=tree.num_actions, lanes=lanes,
                     path_len=sp.path_len, max_depth=sp.max_depth,
                     cp=float(sp.cp), vl_weight=float(sp.vl_weight),
                     puct=bool(sp.puct), wu=bool(getattr(sp, "wu", False)),
                     running=bool(getattr(sp, "running", False)))


def _infl_field(sp) -> str:
    """The arena field backing the kernel's in-flight plane slot."""
    return "unobs" if getattr(sp, "wu", False) else "vloss"


def _planes(tree: TreeArena, sp, wave_valid):
    col = lambda x, dt: x.astype(dt).reshape(-1, 1)
    scal = jnp.stack([tree.next_free.astype(jnp.int32),
                      tree.free_top.astype(jnp.int32),
                      jnp.asarray(wave_valid).astype(jnp.int32).reshape(()),
                      jnp.int32(0)]).reshape(1, 4)
    return {
        "visits": col(tree.visits, jnp.int32),
        "value": col(tree.value, jnp.float32),
        # the mode's in-flight counter plane (WaveCfg.wu docstring): vloss
        # in "loss" mode, the WU-UCT unobs counts in "wu" mode
        "infl": col(getattr(tree, _infl_field(sp)), jnp.int32),
        "prior": tree.prior.astype(jnp.float32),
        "children": tree.children.astype(jnp.int32),
        "terminal": col(tree.terminal, jnp.int32),
        "free_list": col(tree.free_list, jnp.int32),
        "scal": scal,
    }


def _pb(po, num_actions: int):
    """Pack a Playout->Backup buffer for the kernel (6 2-D operands)."""
    return (po["path"].astype(jnp.int32),
            po["value"].astype(jnp.float32)[:, None],
            po["priors"].astype(jnp.float32),
            po["node"].astype(jnp.int32)[:, None],
            po["is_new"].astype(jnp.int32)[:, None],
            po["valid"].astype(jnp.int32)[:, None])


def _empty_pb(sp, lanes: int, num_actions: int):
    from repro.core import stages as S
    return _pb(S.empty_playout(sp, lanes, num_actions), num_actions)


def _unpack_sel(s_leaf, s_depth, s_path, s_dup, valid):
    dup_w, dup_c = s_dup[:, 0] > 0, s_dup[:, 1] > 0
    return {"path": s_path, "leaf": s_leaf[:, 0], "depth": s_depth[:, 0],
            "valid": valid, "dup": dup_w | dup_c,
            "dup_within": dup_w, "dup_cross": dup_c}


def _apply_es(tree: TreeArena, sel_path, sel_depth, leafs,
              e_can, e_slot, e_new, valid):
    """Out-of-launch half of the structural expand: parent/action pointers,
    free-list bookkeeping, path append.  Mirrors ``ref.expand_wave_struct``
    exactly (``new`` already carries the max_nodes drop sentinel)."""
    can = e_can[:, 0] > 0
    slot = e_slot[:, 0]
    new_s = e_new[:, 0]
    lanes = can.shape[0]
    nf0, ft0 = tree.next_free, tree.free_top
    r_total = can.sum().astype(jnp.int32)
    pops = jnp.minimum(r_total, ft0)
    rows = jnp.arange(lanes)
    path = sel_path.at[rows, sel_depth + 1].set(
        jnp.where(can, new_s, UNEXPANDED))
    tree = tree.replace(
        parent=tree.parent.at[new_s].set(leafs, mode="drop"),
        action=tree.action.at[new_s].set(slot, mode="drop"),
        next_free=nf0 + (r_total - pops),
        free_top=ft0 - pops)
    es = {"leaf": leafs, "slot": slot, "new": new_s, "can": can,
          "path": path, "node": jnp.where(can, new_s, leafs),
          "valid": valid}
    return tree, es


def _resolve(sp, impl):
    return impl if impl is not None else sp.resolved_kernels


@jax.named_scope(scopes.TREE)
def tree_round(tree: TreeArena, domain, sp, lanes: int, valid, rng, *,
               impl=None, interpret=False):
    """One fused tree-parallel round.  Pallas path: launch 1 is
    Select→Expand(structural), then the out-of-launch domain finish +
    playout, then launch 2 is Backup.  Returns ``(tree, sel)``."""
    if _resolve(sp, impl) != "pallas":
        return ref.tree_round(tree, domain, sp, lanes, valid, rng)
    from repro.core import stages as S
    cfg = _cfg(tree, sp, lanes)
    wv = jnp.asarray(valid, bool).all()       # kernel waves are all-or-none
    p = _planes(tree, sp, wv)
    (infl, children, s_leaf, s_depth, s_path, s_dup,
     e_can, e_slot, e_new) = K.se_call(
        cfg, p["infl"], p["children"], p["visits"], p["value"], p["prior"],
        p["terminal"], p["free_list"], p["scal"], interpret=interpret)
    valid_vec = jnp.broadcast_to(wv, (lanes,))
    sel = _unpack_sel(s_leaf, s_depth, s_path, s_dup, valid_vec)
    tree = tree.replace(children=children,
                        **{_infl_field(sp): infl[:, 0]})
    tree, es = _apply_es(tree, sel["path"], sel["depth"], sel["leaf"],
                         e_can, e_slot, e_new, valid_vec)
    tree, exp = ref.finish_expand(tree, domain, es)
    po = S.playout_wave(domain, sp, exp, rng)
    p2 = _planes(tree, sp, wv)
    visits, value, infl, prior = K.b_call(
        cfg, p2["visits"], p2["value"], p2["infl"], p2["prior"],
        _pb(po, cfg.a), interpret=interpret)
    tree = tree.replace(visits=visits[:, 0], value=value[:, 0],
                        prior=prior, **{_infl_field(sp): infl[:, 0]})
    return tree, sel


@jax.named_scope(scopes.TREE)
def pipeline_tick(tree: TreeArena, domain, sp, lanes: int, wave_valid,
                  buf_se, buf_ep, buf_pb, rng, *, impl=None,
                  interpret=False):
    """One fused pipeline tick: a single Backup→Expand→Select launch over
    the arena planes, plus the out-of-launch playout and expand finish.
    Returns ``(tree, new_se, new_ep, new_pb)``."""
    if _resolve(sp, impl) != "pallas":
        return ref.pipeline_tick(tree, domain, sp, lanes, wave_valid,
                                 buf_se, buf_ep, buf_pb, rng)
    from repro.core import stages as S
    cfg = _cfg(tree, sp, lanes)
    p = _planes(tree, sp, wave_valid)
    se_leaf = buf_se["leaf"].astype(jnp.int32)[:, None]
    se_valid = buf_se["valid"].astype(jnp.int32)[:, None]
    (visits, value, infl, prior, children,
     s_leaf, s_depth, s_path, s_dup, e_can, e_slot, e_new) = K.bes_call(
        cfg, p["visits"], p["value"], p["infl"], p["prior"], p["children"],
        p["terminal"], p["free_list"], p["scal"], se_leaf, se_valid,
        _pb(buf_pb, cfg.a), interpret=interpret)
    tree = tree.replace(visits=visits[:, 0], value=value[:, 0],
                        prior=prior, children=children,
                        **{_infl_field(sp): infl[:, 0]})
    tree, es = _apply_es(tree, buf_se["path"], buf_se["depth"],
                         buf_se["leaf"], e_can, e_slot, e_new,
                         buf_se["valid"])
    new_pb = S.playout_wave(domain, sp, buf_ep, rng)
    tree, new_ep = ref.finish_expand(tree, domain, es)
    valid_vec = jnp.broadcast_to(jnp.asarray(wave_valid, bool), (lanes,))
    new_se = _unpack_sel(s_leaf, s_depth, s_path, s_dup, valid_vec)
    return tree, new_se, new_ep, new_pb
