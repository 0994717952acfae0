"""Reference (pure-jnp) fused search wave — the megakernel's parity oracle.

The fused wave replaces the per-lane ``lax.scan`` Expand stage with one
vectorized structural pass (``expand_wave_struct``) and keeps Select as the
depth-major lockstep descent.  Everything here is constructed to be
BIT-FOR-BIT equal to scanning ``stages.expand_one`` over the wave:

* slot choice — lane l takes the (k+1)-th UNEXPANDED slot of its leaf's
  *pre-wave* children row, where k counts earlier lanes of the wave that
  expanded the same leaf.  That is exactly the first UNEXPANDED slot of the
  row *as the sequential scan would see it*.
* row allocation — lane l's row is the (r+1)-th pop of the arena's
  allocation order (free-list LIFO first, then the ``next_free`` bump),
  where r counts earlier lanes that allocated.  Capacity runs out for the
  trailing lanes exactly as it would sequentially.

The only remaining sequential piece is an O(lanes) bookkeeping scan over
two small carries ([lanes] i32 + scalar) — the tree planes and the domain
``step`` (the expensive parts) are touched once, vectorized.

``finish_expand`` is the out-of-launch half shared with the Pallas path:
child states come from the *domain* (model calls can't run inside a
kernel), so the kernel emits the structural result (``es``) and this glue
vmaps ``domain.step`` over the wave and scatters state/terminal planes.
Ordering safety: the fused pipeline tick runs Select before
``finish_expand``, which is sound because Select never reads a same-tick
node's state or terminal — a just-expanded node is never fully expanded,
so the descent stops at its parent, and only its visits/vloss/children
(written structurally, in-launch) are consulted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core.arena import UNEXPANDED, TreeArena


@jax.named_scope(scopes.TREE)
def expand_wave_struct(tree: TreeArena, sp, sel):
    """Structural Expand for a whole wave: allocate rows + link children.

    Returns ``(tree, es)`` where ``es`` carries per-lane ``leaf``, chosen
    ``slot`` (action), allocated ``new`` row (max_nodes sentinel when the
    lane couldn't expand), ``can``, the updated ``path``/``node``, and
    ``valid``.  State/terminal of the new rows are NOT written here — see
    ``finish_expand``.
    """
    from repro.core import stages as S
    leafs, depth, valid = sel["leaf"], sel["depth"], sel["valid"]
    n = tree.max_nodes
    lanes = leafs.shape[0]
    base_row = tree.children[leafs]                       # [L, A] pre-wave
    free_m = base_row == UNEXPANDED
    free_cnt = free_m.sum(axis=-1)
    csum = jnp.cumsum(free_m.astype(jnp.int32), axis=-1)
    term = tree.terminal[leafs]
    nf0, ft0 = tree.next_free, tree.free_top
    cap0 = ft0 + (n - nf0)
    same = leafs[:, None] == leafs[None, :]               # same[l, k]

    def body(carry, l):
        taken, r = carry       # taken[m]: wave slots already used at m's leaf
        can = valid[l] & ~term[l] & (free_cnt[l] > taken[l]) & (r < cap0)
        # (taken[l]+1)-th UNEXPANDED slot == first free slot the sequential
        # scan would see after the earlier same-leaf lanes wrote theirs
        slot = jnp.argmax(free_m[l] & (csum[l] == taken[l] + 1)) \
            .astype(jnp.int32)
        new = jnp.where(
            r < ft0,
            tree.free_list[jnp.clip(ft0 - 1 - r, 0, n - 1)],
            nf0 + (r - ft0)).astype(jnp.int32)
        taken = taken + (same[l] & can).astype(jnp.int32)
        r = r + can.astype(jnp.int32)
        return (taken, r), (can, slot, new)

    (_, r_total), (can, slot, new) = jax.lax.scan(
        body, (jnp.zeros((lanes,), jnp.int32), jnp.asarray(0, jnp.int32)),
        jnp.arange(lanes))

    new_s = jnp.where(can, new, n).astype(jnp.int32)       # OOB -> dropped
    pops = jnp.minimum(r_total, ft0)
    rows = jnp.arange(lanes)
    path = sel["path"].at[rows, depth + 1].set(
        jnp.where(can, new, UNEXPANDED))
    infl = S.infl_plane(tree, sp).at[new_s].add(1, mode="drop")
    tree = S.with_infl(tree, sp, infl).replace(
        children=tree.children.at[
            jnp.where(can, leafs, n), slot].set(new, mode="drop"),
        parent=tree.parent.at[new_s].set(leafs, mode="drop"),
        action=tree.action.at[new_s].set(slot, mode="drop"),
        next_free=nf0 + (r_total - pops),
        free_top=ft0 - pops)
    es = {"leaf": leafs, "slot": slot, "new": new_s, "can": can,
          "path": path, "node": jnp.where(can, new_s, leafs),
          "valid": valid}
    return tree, es


@jax.named_scope(scopes.TREE)
def finish_expand(tree: TreeArena, domain, es):
    """Domain half of Expand (outside any kernel): vmap ``domain.step`` over
    the wave, scatter the new rows' state/terminal, and assemble the
    Expand->Playout buffer.  Shared by the ref and Pallas fused paths."""
    with jax.named_scope(scopes.NODE_STATE):
        parent_state = jax.tree_util.tree_map(
            lambda x: x[es["leaf"]], tree.state)
    with jax.named_scope(scopes.EXPAND):
        child_state = jax.vmap(domain.step)(parent_state, es["slot"])
        term = jax.vmap(domain.is_terminal)(child_state)
    can, new = es["can"], es["new"]
    with jax.named_scope(scopes.NODE_STATE):
        tree = tree.replace(
            state=jax.tree_util.tree_map(
                lambda buf, s: buf.at[new].set(s, mode="drop"),
                tree.state, child_state))
        state = jax.tree_util.tree_map(
            lambda s_par, s_ch: jnp.where(
                jnp.reshape(can, can.shape + (1,) * (jnp.ndim(s_ch) - 1)),
                s_ch, s_par),
            parent_state, child_state)
    tree = tree.replace(terminal=tree.terminal.at[new].set(term, mode="drop"))
    return tree, {"path": es["path"], "node": es["node"], "is_new": can,
                  "state": state, "valid": es["valid"]}


def tree_round(tree: TreeArena, domain, sp, lanes: int, valid, rng):
    """Fused tree-parallel round (ref): lockstep Select -> vectorized
    structural Expand -> domain finish -> Playout -> Backup."""
    from repro.core import stages as S
    tree, sel = S.select_wave_fused(tree, sp, lanes, valid)
    tree, es = expand_wave_struct(tree, sp, sel)
    tree, exp = finish_expand(tree, domain, es)
    po = S.playout_wave(domain, sp, exp, rng)
    tree = S.backup_wave(tree, po, sp)
    return tree, sel


def pipeline_tick(tree: TreeArena, domain, sp, lanes: int, wave_valid,
                  buf_se, buf_ep, buf_pb, rng):
    """Fused pipeline tick (ref): B(wave t-3) -> P(wave t-2) -> E(wave t-1,
    structural + finish) -> S(wave t) — the same stage order as the
    unfused tick, with Expand's per-lane scan replaced by the vectorized
    structural pass."""
    from repro.core import stages as S
    tree = S.backup_wave(tree, buf_pb, sp)
    new_pb = S.playout_wave(domain, sp, buf_ep, rng)
    tree, es = expand_wave_struct(tree, sp, buf_se)
    tree, new_ep = finish_expand(tree, domain, es)
    tree, new_se = S.select_wave_fused(tree, sp, lanes, wave_valid)
    return tree, new_se, new_ep, new_pb
