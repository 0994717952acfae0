"""Pallas TPU fused search-wave megakernel (DESIGN.md §14).

One launch drives a whole wave against the VMEM-resident arena planes
instead of a kernel launch per tree level:

* ``se_call``  — Select(lockstep descent, every level in-kernel) →
  Expand(structural allocation) for the tree strategy's round.
* ``bes_call`` — Backup(wave t-3) → Expand(wave t-1, structural) →
  Select(wave t) for the pipeline tick: three stages, one launch.
* ``b_call``   — Backup alone (the tree round's second launch, after the
  out-of-kernel playout).

The *domain* half of Expand (model ``step``/``is_terminal``) cannot run
inside a kernel; the kernel emits the structural result (can/slot/new row
per lane) and ``ref.finish_expand`` completes state/terminal outside the
launch.  Running Select ahead of that finish is sound — see ``ref.py``.

Layout notes (guide: 1-D iota is unsupported on TPU — all index vectors
come from ``broadcasted_iota``; scalars ride in one ``[1, 4]`` word;
gathers use one-hot ``precision=HIGHEST`` dots so integer planes
round-trip exactly below 2^24; per-lane values are [L, 1] columns and no
value is ever an address — see the phase helpers).  Grid is () — every
plane fits one VMEM block at search-tree sizes; the mutable planes are
input/output aliased so the launch updates them in place.

Parity contract: phases mirror ``core.stages`` formula-for-formula
(including the 1e30 unvisited clamp, the -1e30 invalid mask, first-max
argmax tie-breaking, and the per-level own-virtual-loss exclusion), so the
launch is bit-for-bit equal to the lockstep path at ``lanes == 1`` and
integer-exact at any width; float backup sums may differ in the last ulp
at ``lanes > 1`` only where a node takes multiple same-wave contributions.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.parallel.compat import tpu_compiler_params

UNEXPANDED = -1
ROOT = 0
NEG_INF = -1e30          # python literal: jnp constants can't be captured
                         # inside a pallas kernel body
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class WaveCfg:
    """Static shape/knob bundle threaded through the phase helpers.

    ``wu`` selects the in-flight statistics mode (DESIGN.md §15).  The
    launch carries ONE in-flight plane operand (the ``vloss``-named slot):
    ops.py stages ``tree.vloss`` there in "loss" mode and ``tree.unobs``
    (the WU-UCT O counts) in "wu" mode — increments fused into the descent
    /expand, decrements fused into backup, input/output-aliased either way.
    Only the scoring formula branches on ``wu``; the inactive plane is
    all-zeros and never enters the kernel.
    """
    n: int            # max_nodes
    a: int            # num_actions
    lanes: int
    path_len: int
    max_depth: int
    cp: float
    vl_weight: float
    puct: bool
    wu: bool = False
    running: bool = False   # within-level running assignment (DESIGN.md §16)


def _iota(rows: int, cols: int, dim: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, cols), dim)


def _onehot(idx, n):
    """[K, 1] i32 -> [K, n] f32 one-hot (0 where idx is out of range)."""
    return (_iota(idx.shape[0], n, 1) == idx).astype(jnp.float32)


def _gather_vec(v, idx):
    """v [N, 1] f32, idx [K, 1] -> [K, 1] f32 (exact for integer-valued
    v < 2^24)."""
    return jax.lax.dot_general(_onehot(idx, v.shape[0]), v,
                               (((1,), (0,)), ((), ())), precision=_HI)


def _gather_rows(m, idx):
    """m [N, C] f32, idx [K, 1] -> [K, C] f32."""
    return jax.lax.dot_general(_onehot(idx, m.shape[0]), m,
                               (((1,), (0,)), ((), ())), precision=_HI)


def _gather_mat(v, idx):
    """v [N, 1] f32, idx [K, C] -> [K, C] f32: one column gather per child
    slot (C is the action count, small), so no reshape crosses the tiles."""
    k, c = idx.shape
    iota_c = _iota(k, c, 1)
    out = jnp.zeros((k, c), jnp.float32)
    for j in range(c):
        out = jnp.where(iota_c == j, _gather_vec(v, idx[:, j:j + 1]), out)
    return out


def _pick(m, rowsel):
    """[K, C], [K, 1] one-row mask -> that row, [1, C] (exact)."""
    return jnp.where(rowsel, m, 0).sum(axis=0, keepdims=True)


def _scal(scal, j):
    """Column ``j`` of the [1, 4] scalar word as a [1, 1] i32 vector."""
    return jnp.where(_iota(1, 4, 1) == j, scal, 0).sum(axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# phase helpers (operate on refs/values; mirror core.stages bit-for-bit)
#
# Layout: per-lane values are [L, 1] columns and planes [N, 1] / [N, A].
# A lane's scalar is read as a [1, 1] vector through a one-row mask and a
# node's row through a mask over the whole plane: no value is ever used as
# an address and no reshape crosses the (8, 128) tiles, so every phase
# lowers for the TPU as it runs in the interpreter.
# ---------------------------------------------------------------------------
def _backup_phase(cfg: WaveCfg, visits_ref, value_ref, vloss_ref, prior_ref,
                  pb_path, pb_value, pb_isnew, pb_node, pb_priors, pb_valid):
    """Lane by lane, like ``stages.backup_wave``'s scatter-add: a path holds
    each node at most once, so lane l adds 1 visit and its value to each
    node on its path — in lane order, the scatter's order."""
    l, n = cfg.lanes, cfg.n
    iota_l = _iota(l, 1, 0)
    iota_n = _iota(n, 1, 0)

    def body(i, carry):
        rowsel = iota_l == i
        path = _pick(pb_path, rowsel)                      # [1, P]
        ok = _pick(pb_valid, rowsel) > 0                   # [1, 1]
        # widen [1, 1] along one axis at a time: Mosaic cannot broadcast a
        # [1, 1] over sublanes and lanes at once
        on_path = (path >= 0) & ok                         # [1, P]
        hit = ((iota_n == path) & on_path).astype(jnp.int32)
        cnt = hit.sum(axis=1, keepdims=True)               # [N, 1] 0/1
        visits_ref[...] = visits_ref[...] + cnt
        value_ref[...] = value_ref[...] + jnp.where(
            cnt > 0, _pick(pb_value, rowsel), 0.0)
        vloss_ref[...] = vloss_ref[...] - cnt
        # priors for freshly created nodes (distinct rows across lanes)
        fresh = ok & (_pick(pb_isnew, rowsel) > 0)
        written = (iota_n == _pick(pb_node, rowsel)) & fresh   # [N, 1]
        prior_ref[...] = jnp.where(written, _pick(pb_priors, rowsel),
                                   prior_ref[...])
        return carry

    jax.lax.fori_loop(0, l, body, 0)


def _expand_phase(cfg: WaveCfg, children_ref, vloss_ref, term_leaf,
                  free_list_ref, nf0, ft0, leafs, valid):
    """Sequential-semantics structural expand: a fori over lanes reading the
    live children rows, exactly like scanning ``stages.expand_one``.
    ``leafs``/``term_leaf``/``valid`` are [L, 1] and ``nf0``/``ft0`` [1, 1];
    returns can/slot/new as [L, 1] i32."""
    l, n, a = cfg.lanes, cfg.n, cfg.a
    cap0 = ft0 + (n - nf0)
    iota_l = _iota(l, 1, 0)
    iota_n = _iota(n, 1, 0)
    iota_a = _iota(1, a, 1)
    leaf_c = leafs.astype(jnp.int32)
    term_c = term_leaf.astype(jnp.int32)
    valid_c = valid.astype(jnp.int32)
    free_list = free_list_ref[...]                         # [N, 1]

    def body(i, carry):
        r, can_acc, slot_acc, new_acc = carry              # r [1, 1]
        rowsel = iota_l == i
        at_leaf = iota_n == _pick(leaf_c, rowsel)          # [N, 1]
        children = children_ref[...]
        row = _pick(children, at_leaf)                     # live row [1, A]
        free = row == UNEXPANDED
        has_slot = free.astype(jnp.int32).max(axis=1, keepdims=True) > 0
        can = ((_pick(valid_c, rowsel) > 0) & has_slot
               & ~(_pick(term_c, rowsel) > 0) & (r < cap0))
        slot = jnp.min(jnp.where(free, iota_a, a), axis=1, keepdims=True)
        slot = jnp.minimum(slot, a - 1)
        pop_row = jnp.clip(ft0 - 1 - r, 0, n - 1)
        new = jnp.where(r < ft0, _pick(free_list, iota_n == pop_row),
                        nf0 + (r - ft0))
        # link the child + its in-flight virtual loss (masked row stores)
        new_row = jnp.where((iota_a == slot) & can, new, row)
        children_ref[...] = jnp.where(at_leaf, new_row, children)
        nc = jnp.clip(new, 0, n - 1)
        vloss_ref[...] = vloss_ref[...] + ((iota_n == nc) & can).astype(
            jnp.int32)
        r = r + can.astype(jnp.int32)
        can_acc = jnp.where(rowsel, can.astype(jnp.int32), can_acc)
        slot_acc = jnp.where(rowsel, slot, slot_acc)
        new_acc = jnp.where(rowsel, jnp.where(can, new, n), new_acc)
        return r, can_acc, slot_acc, new_acc

    zeros = jnp.zeros((l, 1), jnp.int32)
    _, can, slot, new_s = jax.lax.fori_loop(
        0, l, body, (jnp.zeros((1, 1), jnp.int32), zeros, zeros, zeros))
    # terminal gather is done against leaf *indices* by the caller
    return can, slot, new_s


def _lane_counts(idx, mask, n):
    """[L, 1] node ids and 0/1 mask -> [N, 1] i32 occurrence counts."""
    l = idx.shape[0]
    iota_l = _iota(l, 1, 0)
    iota_n = _iota(n, 1, 0)

    def body(i, acc):
        rowsel = iota_l == i
        hit = (iota_n == _pick(idx, rowsel)) & (_pick(mask, rowsel) > 0)
        return acc + hit.astype(jnp.int32)

    return jax.lax.fori_loop(0, l, body, jnp.zeros((n, 1), jnp.int32))


def _select_phase(cfg: WaveCfg, vloss_ref, visits_v, value_v, prior_v,
                  children_v, terminal_v, wave_valid):
    """Lockstep descent, every level in-kernel (mirrors
    ``stages.select_wave_fused``).  Planes are [N, 1] / [N, A] f32,
    ``wave_valid`` [1, 1]; per-lane results are [L, 1]."""
    l, n, a, p = cfg.lanes, cfg.n, cfg.a, cfg.path_len
    iota_l = _iota(l, 1, 0)
    iota_n = _iota(n, 1, 0)
    valid = jnp.broadcast_to(wave_valid > 0, (l, 1))
    vloss_pre = vloss_ref[...].astype(jnp.float32)         # pre-wave, for dup
    nvalid = valid.astype(jnp.int32).sum(axis=0, keepdims=True)
    vloss_ref[...] = vloss_ref[...] + jnp.where(iota_n == ROOT, nvalid, 0)

    def lane_active(node, depth):
        ch = _gather_rows(children_v, node)
        fully = ch.min(axis=1, keepdims=True) >= -0.5      # all children >= 0
        term = _gather_vec(terminal_v, node) > 0.5
        return fully & ~term & (depth < cfg.max_depth)

    node0 = jnp.zeros((l, 1), jnp.int32)
    depth0 = jnp.zeros((l, 1), jnp.int32)
    path0 = jnp.where(_iota(l, p, 1) == 0, ROOT, UNEXPANDED)
    active0 = valid & lane_active(node0, depth0)
    iota_a = _iota(l, a, 1)
    iota_p = _iota(l, p, 1)

    def body(_, c):           # bool vectors cannot ride a loop carry
        node, depth, path, active_i = c
        active = active_i > 0
        chf = _gather_rows(children_v, node)               # [L, A] f32
        ch = chf.astype(jnp.int32)
        idx = jnp.maximum(ch, 0)
        vloss_v = vloss_ref[...].astype(jnp.float32)
        own = active.astype(jnp.int32)
        cn = _gather_mat(visits_v, idx)
        cw = _gather_mat(value_v, idx)
        cvl = _gather_mat(vloss_v, idx)
        pn = (_gather_vec(visits_v, node) + _gather_vec(vloss_v, node)
              - own.astype(jnp.float32))                   # [L, 1]
        pr = _gather_rows(prior_v, node) if cfg.puct else None
        # uct_scores, formula-for-formula (core.uct); in "wu" mode cvl holds
        # the gathered O counts — they widen exploration only, Q is computed
        # from completed statistics alone
        if cfg.running:
            # Running assignment (DESIGN.md §16): a sequential lane walk —
            # lane i scores with a running delta already incremented by the
            # picks of co-located lanes < i at this level.  The delta joins
            # cvl (the mode's staged in-flight plane), so it widens
            # exploration in "wu" mode and also corrupts Q in "loss" mode,
            # exactly like the jnp lane scan.  One launch per level still.
            iota_1a = _iota(1, a, 1)
            activef = active.astype(jnp.float32)           # [L, 1]

            def assign(i, carry):
                delta, sel_acc = carry
                rowsel = iota_l == i                       # [L, 1]
                rs = rowsel.astype(jnp.float32)
                row = lambda x: (x * rs).sum(axis=0, keepdims=True)
                cvl_eff = row(cvl) + row(delta)            # [1, A]
                cn_i, cw_i = row(cn), row(cw)
                n_eff = cn_i + cvl_eff
                pnc = jnp.maximum(row(pn), 1.0)            # [1, 1]
                if cfg.wu:
                    q = cw_i / jnp.maximum(cn_i, 1.0)
                else:
                    q = (cw_i - cfg.vl_weight * cvl_eff) \
                        / jnp.maximum(n_eff, 1.0)
                if cfg.puct:
                    explore = row(pr) * jnp.sqrt(pnc) / (1.0 + n_eff)
                else:
                    explore = jnp.sqrt(jnp.log(pnc)
                                       / jnp.maximum(n_eff, 1.0))
                s = q + cfg.cp * explore
                s = jnp.where(n_eff < 0.5, 1e30, s)
                ch_i = _pick(ch, rowsel)                   # [1, A]
                act_i = row(activef) > 0.5                 # [1, 1]
                s = jnp.where((ch_i >= 0) & act_i, s, NEG_INF)
                sel_i = jnp.argmax(s, axis=1, keepdims=True).astype(
                    jnp.int32)                             # [1, 1]
                oh = (iota_1a == sel_i).astype(jnp.float32)
                share = (node == _pick(node, rowsel)) & act_i   # [L, 1]
                delta = delta + share.astype(jnp.float32) * oh
                sel_acc = jnp.where(rowsel, sel_i, sel_acc)
                return delta, sel_acc

            _, sel_a = jax.lax.fori_loop(
                0, l, assign,
                (jnp.zeros((l, a), jnp.float32), jnp.zeros((l, 1),
                                                           jnp.int32)))
        else:
            n_eff = cn + cvl
            pnc = jnp.maximum(pn, 1.0)
            if cfg.wu:
                q = cw / jnp.maximum(cn, 1.0)
            else:
                q = (cw - cfg.vl_weight * cvl) / jnp.maximum(n_eff, 1.0)
            if cfg.puct:
                explore = pr * jnp.sqrt(pnc) / (1.0 + n_eff)
            else:
                explore = jnp.sqrt(jnp.log(pnc) / jnp.maximum(n_eff, 1.0))
            s = q + cfg.cp * explore
            s = jnp.where(n_eff < 0.5, 1e30, s)
            s = jnp.where((ch >= 0) & active, s, NEG_INF)
            sel_a = jnp.argmax(s, axis=1, keepdims=True).astype(jnp.int32)
        nxt = jnp.where(iota_a == sel_a, ch, 0).sum(axis=1, keepdims=True)
        col = jnp.where(active, depth + 1, p)
        path = jnp.where(iota_p == col, nxt, path)
        vloss_ref[...] = vloss_ref[...] + _lane_counts(nxt, own, n)
        node = jnp.where(active, nxt, node)
        depth = depth + own
        active = active & lane_active(node, depth)
        return node, depth, path, active.astype(jnp.int32)

    leaf, depth, path, _ = jax.lax.fori_loop(
        0, cfg.max_depth, body,
        (node0, depth0, path0, active0.astype(jnp.int32)))

    def earlier(j, shared):        # a lower-numbered lane shares this leaf
        return shared | ((leaf == _pick(leaf, iota_l == j))
                         & (iota_l > j)).astype(jnp.int32)

    shared = jax.lax.fori_loop(0, l, earlier, jnp.zeros((l, 1), jnp.int32))
    dup_w = (shared > 0) & valid                           # within this wave
    dup_c = (_gather_vec(vloss_pre, leaf) > 0.5) & valid
    path = jnp.where(valid, path, UNEXPANDED)
    return leaf, depth, path, dup_w, dup_c, valid


def _store_sel(s_leaf, s_depth, s_path, s_dup, leaf, depth, path, dup_w,
               dup_c):
    s_leaf[...] = leaf
    s_depth[...] = depth
    s_path[...] = path
    # [L, 2]: col 0 = within-wave shared leaf, col 1 = cross-wave in-flight
    s_dup[...] = jnp.where(_iota(leaf.shape[0], 2, 1) == 0,
                           dup_w.astype(jnp.int32), dup_c.astype(jnp.int32))


def _store_es(e_can, e_slot, e_new, can, slot, new_s):
    e_can[...] = can
    e_slot[...] = slot
    e_new[...] = new_s


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _se_kernel(vloss_in, children_in, visits, value, prior, terminal,
               free_list, scal, vloss_o, children_o,
               s_leaf, s_depth, s_path, s_dup, e_can, e_slot, e_new, *,
               cfg: WaveCfg):
    del vloss_in, children_in                  # aliased into the outputs
    visits_v = visits[...].astype(jnp.float32)
    value_v = value[...]
    prior_v = prior[...]
    terminal_v = terminal[...].astype(jnp.float32)
    children_v = children_o[...].astype(jnp.float32)   # pre-expand snapshot
    sc = scal[...]
    leaf, depth, path, dup_w, dup_c, valid = _select_phase(
        cfg, vloss_o, visits_v, value_v, prior_v, children_v, terminal_v,
        _scal(sc, 2))
    _store_sel(s_leaf, s_depth, s_path, s_dup, leaf, depth, path, dup_w,
               dup_c)
    term_leaf = _gather_vec(terminal_v, leaf) > 0.5
    can, slot, new_s = _expand_phase(
        cfg, children_o, vloss_o, term_leaf, free_list,
        _scal(sc, 0), _scal(sc, 1), leaf, valid)
    _store_es(e_can, e_slot, e_new, can, slot, new_s)


def _bes_kernel(visits_in, value_in, vloss_in, prior_in, children_in,
                terminal, free_list, scal, se_leaf, se_valid,
                pb_path, pb_value, pb_priors, pb_node, pb_isnew, pb_valid,
                visits_o, value_o, vloss_o, prior_o, children_o,
                s_leaf, s_depth, s_path, s_dup, e_can, e_slot, e_new, *,
                cfg: WaveCfg):
    del visits_in, value_in, vloss_in, prior_in, children_in   # aliased
    _backup_phase(cfg, visits_o, value_o, vloss_o, prior_o,
                  pb_path[...], pb_value[...], pb_isnew[...], pb_node[...],
                  pb_priors[...], pb_valid[...])
    terminal_v = terminal[...].astype(jnp.float32)
    leafs = se_leaf[...]
    e_valid = se_valid[...] > 0
    term_leaf = _gather_vec(terminal_v, leafs) > 0.5
    sc = scal[...]
    can, slot, new_s = _expand_phase(
        cfg, children_o, vloss_o, term_leaf, free_list,
        _scal(sc, 0), _scal(sc, 1), leafs, e_valid)
    _store_es(e_can, e_slot, e_new, can, slot, new_s)
    # Select reads children AFTER the structural expand (same tick order as
    # the unfused pipeline); new rows are never descended into (not fully
    # expanded), so their unwritten state/terminal are never consulted.
    visits_v = visits_o[...].astype(jnp.float32)
    value_v = value_o[...]
    prior_v = prior_o[...]
    children_v = children_o[...].astype(jnp.float32)
    leaf, depth, path, dup_w, dup_c, _ = _select_phase(
        cfg, vloss_o, visits_v, value_v, prior_v, children_v, terminal_v,
        _scal(sc, 2))
    _store_sel(s_leaf, s_depth, s_path, s_dup, leaf, depth, path, dup_w,
               dup_c)


def _b_kernel(visits_in, value_in, vloss_in, prior_in,
              pb_path, pb_value, pb_priors, pb_node, pb_isnew, pb_valid,
              visits_o, value_o, vloss_o, prior_o, *, cfg: WaveCfg):
    del visits_in, value_in, vloss_in, prior_in               # aliased
    _backup_phase(cfg, visits_o, value_o, vloss_o, prior_o,
                  pb_path[...], pb_value[...], pb_isnew[...], pb_node[...],
                  pb_priors[...], pb_valid[...])


# ---------------------------------------------------------------------------
# launch wrappers (2-D plane packing; ops.py owns arena <-> plane plumbing)
# ---------------------------------------------------------------------------
def _call(kernel, name, ins, out_shapes, aliases, interpret):
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        compiler_params=tpu_compiler_params(dimension_semantics=()),
        interpret=interpret,
        name=name,
    )(*ins)


def _sel_out_shapes(cfg: WaveCfg):
    l, p = cfg.lanes, cfg.path_len
    return [jax.ShapeDtypeStruct((l, 1), jnp.int32),      # leaf
            jax.ShapeDtypeStruct((l, 1), jnp.int32),      # depth
            jax.ShapeDtypeStruct((l, p), jnp.int32),      # path
            jax.ShapeDtypeStruct((l, 2), jnp.int32)]      # dup within|cross


def _es_out_shapes(cfg: WaveCfg):
    l = cfg.lanes
    return [jax.ShapeDtypeStruct((l, 1), jnp.int32)] * 3  # can, slot, new


def se_call(cfg: WaveCfg, vloss, children, visits, value, prior, terminal,
            free_list, scal, *, interpret=False):
    """Select→Expand launch; returns (vloss, children, sel..., es...)."""
    outs = ([jax.ShapeDtypeStruct(vloss.shape, vloss.dtype),
             jax.ShapeDtypeStruct(children.shape, children.dtype)]
            + _sel_out_shapes(cfg) + _es_out_shapes(cfg))
    return _call(functools.partial(_se_kernel, cfg=cfg), "search_wave_se",
                 [vloss, children, visits, value, prior, terminal,
                  free_list, scal],
                 outs, {0: 0, 1: 1}, interpret)


def bes_call(cfg: WaveCfg, visits, value, vloss, prior, children, terminal,
             free_list, scal, se_leaf, se_valid, pb, *, interpret=False):
    """Backup→Expand→Select launch (one pipeline tick's tree mutations)."""
    outs = ([jax.ShapeDtypeStruct(x.shape, x.dtype)
             for x in (visits, value, vloss, prior, children)]
            + _sel_out_shapes(cfg) + _es_out_shapes(cfg))
    return _call(functools.partial(_bes_kernel, cfg=cfg), "search_wave_bes",
                 [visits, value, vloss, prior, children, terminal, free_list,
                  scal, se_leaf, se_valid] + list(pb),
                 outs, {i: i for i in range(5)}, interpret)


def b_call(cfg: WaveCfg, visits, value, vloss, prior, pb, *,
           interpret=False):
    """Backup-only launch; returns the four updated planes."""
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
            for x in (visits, value, vloss, prior)]
    return _call(functools.partial(_b_kernel, cfg=cfg), "search_wave_b",
                 [visits, value, vloss, prior] + list(pb),
                 outs, {i: i for i in range(4)}, interpret)
