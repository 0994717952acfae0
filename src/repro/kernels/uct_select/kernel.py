"""Pallas TPU fused UCT score + masked argmax over children tiles.

The Select stage's hot op (paper eq. 1): for a batch of R tree nodes with A
children each, compute UCT scores with virtual loss and return the best child
index per node — fused in VMEM, no [R, A] score array round-trip through HBM.
Action width is lane-padded to 128 by the ops layer.

R is the wave axis: the lockstep Select stage (DESIGN.md §11) issues ONE
launch per tree level with R = lanes, so a whole wave's children score in a
single [R, 128·k] VMEM tile instead of R single-row launches.  Rows may
duplicate a parent (co-located lanes) and rows whose ``valid`` mask is all
zero (finished lanes) argmax over -inf to index 0, which callers discard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.compat import tpu_compiler_params

NEG_INF = -1e30


def _uct_kernel(n_ref, w_ref, vl_ref, uo_ref, pn_ref, valid_ref, out_ref, *,
                cp: float, vl_weight: float, wu: bool):
    n = n_ref[...].astype(jnp.float32)           # [BLK_R, A]
    w = w_ref[...]
    vl = vl_ref[...].astype(jnp.float32)
    uo = uo_ref[...].astype(jnp.float32)         # [BLK_R, A] unobs counts O
    pn = pn_ref[...].astype(jnp.float32)         # [BLK_R, 1]
    valid = valid_ref[...]                       # [BLK_R, A] int32 mask
    if wu:
        # WU-UCT: O widens exploration only; Q from completed stats.
        n_eff = n + uo
        q = w / jnp.maximum(n, 1.0)
    else:
        n_eff = n + vl
        q = (w - vl_weight * vl) / jnp.maximum(n_eff, 1.0)
    explore = jnp.sqrt(jnp.log(jnp.maximum(pn, 1.0)) / jnp.maximum(n_eff, 1.0))
    s = q + cp * explore
    s = jnp.where(n_eff < 0.5, 1e30, s)          # idle unvisited -> must explore
    s = jnp.where(valid > 0, s, NEG_INF)
    # first-max argmax: sentinel ties resolve to the lowest index (ref parity)
    out_ref[...] = jnp.argmax(s, axis=1, keepdims=True).astype(jnp.int32)


def uct_argmax_tiles(child_n, child_w, child_vl, child_o, parent_n, valid, *,
                     cp: float, vl_weight: float, wu: bool = False,
                     blk_r: int = 256, interpret: bool = False):
    """All [R, A] (A lane-padded); parent_n [R, 1] -> best index [R, 1] i32."""
    r, a = child_n.shape
    nr = pl.cdiv(r, blk_r)
    kernel = functools.partial(_uct_kernel, cp=cp, vl_weight=vl_weight, wu=wu)
    row = lambda i: (i, 0)
    return pl.pallas_call(
        kernel,
        grid=(nr,),
        in_specs=[pl.BlockSpec((blk_r, a), row) for _ in range(4)]
        + [pl.BlockSpec((blk_r, 1), row), pl.BlockSpec((blk_r, a), row)],
        out_specs=pl.BlockSpec((blk_r, 1), row),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
        name="uct_select",
    )(child_n, child_w, child_vl, child_o, parent_n, valid)


def _uct_running_kernel(n_ref, w_ref, vl_ref, uo_ref, pn_ref, valid_ref,
                        pid_ref, out_ref, *, cp: float, vl_weight: float,
                        wu: bool):
    """Running-assignment wave argmax (DESIGN.md §16): a sequential row walk
    inside ONE launch.  Row i scores with a running in-flight accumulator
    already incremented by the picks of rows 0..i-1 that share row i's
    parent id — dup-parent rows share one accumulator; rows with a distinct
    parent are untouched.  The accumulator joins ``vl`` in loss mode (Q and
    effective count) and ``uo`` in wu mode (exploration only).  A row whose
    ``valid`` mask is all zero contributes nothing and returns index 0.
    Rows are extracted with masked reductions (no dynamic row slicing), so
    the walk is O(R^2·A) VPU work — R is the wave's lane count, small.
    """
    n = n_ref[...].astype(jnp.float32)               # [R, A]
    w = w_ref[...]
    vl = vl_ref[...].astype(jnp.float32)
    uo = uo_ref[...].astype(jnp.float32)
    pn = pn_ref[...].astype(jnp.float32)             # [R, 1]
    valid = valid_ref[...]                           # [R, A] int32 mask
    pid = pid_ref[...]                               # [R, 1] int32 parent ids
    r, a = n.shape
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    iota_a1 = jax.lax.broadcasted_iota(jnp.int32, (1, a), 1)
    activef = (valid.sum(axis=1, keepdims=True) > 0).astype(jnp.float32)

    def body(i, carry):
        contrib, out = carry
        rowsel = iota_r == i                         # [R, 1]
        rs = rowsel.astype(jnp.float32)
        row = lambda x: (x * rs).sum(axis=0, keepdims=True)
        d_i = row(contrib)                           # [1, A] running counts
        n_i, w_i = row(n), row(w)
        va_i = row(valid.astype(jnp.float32))
        pn_i = row(pn)                               # [1, 1]
        if wu:
            n_eff = n_i + (row(uo) + d_i)
            q = w_i / jnp.maximum(n_i, 1.0)
        else:
            vle = row(vl) + d_i
            n_eff = n_i + vle
            q = (w_i - vl_weight * vle) / jnp.maximum(n_eff, 1.0)
        explore = jnp.sqrt(jnp.log(jnp.maximum(pn_i, 1.0))
                           / jnp.maximum(n_eff, 1.0))
        s = q + cp * explore
        s = jnp.where(n_eff < 0.5, 1e30, s)
        s = jnp.where(va_i > 0, s, NEG_INF)
        sel = jnp.argmax(s, axis=1).astype(jnp.int32)    # [1], first-max
        onehot = (iota_a1 == sel[:, None]).astype(jnp.float32)
        pid_i = (pid * rowsel.astype(jnp.int32)).sum(axis=0, keepdims=True)
        act_i = row(activef)[0, 0] > 0.5
        share = ((pid == pid_i) & act_i).astype(jnp.float32)   # [R, 1]
        contrib = contrib + share * onehot
        out = jnp.where(rowsel, sel[:, None], out)
        return contrib, out

    _, out = jax.lax.fori_loop(
        0, r, body,
        (jnp.zeros((r, a), jnp.float32), jnp.zeros((r, 1), jnp.int32)))
    out_ref[...] = out


def uct_argmax_running_call(child_n, child_w, child_vl, child_o, parent_n,
                            valid, parent_id, *, cp: float, vl_weight: float,
                            wu: bool = False, interpret: bool = False):
    """All [R, A] (A lane-padded), parent_n/parent_id [R, 1] -> [R, 1] i32.
    Whole-array blocks, single launch: the running walk needs every row of
    the wave in one tile (no ``blk_r`` grid — R is a lane count)."""
    r, _ = child_n.shape
    kernel = functools.partial(_uct_running_kernel, cp=cp,
                               vl_weight=vl_weight, wu=wu)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        compiler_params=tpu_compiler_params(dimension_semantics=()),
        interpret=interpret,
        name="uct_select_running",
    )(child_n, child_w, child_vl, child_o, parent_n, valid, parent_id)
