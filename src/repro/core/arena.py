"""Typed structure-of-arrays tree arena with node recycling (DESIGN.md §14).

``TreeArena`` is the single tree representation behind ``core.tree``,
``core.stages``, every search strategy, and serving's cross-token
``tree_reuse`` carry.  It is a frozen dataclass registered as a jax pytree,
so it jits/vmaps/scans exactly like the raw dict it replaces, while giving
the planes a typed, documented layout:

    visits    [N] i32     visit count n_j
    value     [N] f32     reward sum  w_j
    vloss     [N] i32     virtual-loss counters (in-flight trajectories,
                          ``vl_mode="loss"``)
    unobs     [N] i32     WU-UCT unobserved-sample counters O_j — playouts
                          initiated but not yet backed up through the node
                          (``vl_mode="wu"``; DESIGN.md §15)
    parent    [N] i32     parent index (-1 for root / unallocated / freed)
    action    [N] i32     action taken from parent
    children  [N, A] i32  child indices (UNEXPANDED = -1)
    prior     [N, A] f32  child priors (uniform UCT / policy PUCT)
    terminal  [N] bool    node is a terminal state
    state     pytree      per-node domain state, leading dim N
    next_free scalar i32  bump-allocation high-water mark
    free_list [N] i32     LIFO stack of recycled row indices
    free_top  scalar i32  live depth of ``free_list``

Allocation contract (the free-list is what lets ``reroot`` recycle the
abandoned sibling subtrees instead of leaking rows across a serving
request's lifetime):

* ``alloc`` pops ``free_list[free_top - 1]`` when the stack is non-empty,
  else bumps ``next_free``.  Capacity is exhausted only when the stack is
  empty AND ``next_free == N`` — searches then stop expanding gracefully
  (``ok`` comes back False) instead of corrupting rows.
* ``release`` pushes rows onto the stack and resets their planes to the
  unallocated state (parent = -1, children = UNEXPANDED, uniform prior),
  so a recycled row is indistinguishable from a never-used one.
* ``compact``/``reroot`` rebuild the bookkeeping wholesale: live rows are
  renumbered densely from the (new) root, ``next_free`` drops to the live
  count and the stack empties — occupancy is bounded by the live subtree,
  not by search history.

A row is *live* iff it is the root or has ``parent >= 0`` (``live_mask``).
``ROOT`` is always row 0; ``compact`` preserves that invariant.

Dict-style ``arena["visits"]`` access still works for one release via
``__getitem__`` (with a ``DeprecationWarning``) so downstream code written
against the old ``Dict[str, Any]`` tree keeps running; new code should use
the attributes.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import scopes

UNEXPANDED = -1
ROOT = 0

_FIELDS = ("visits", "value", "vloss", "unobs", "parent", "action",
           "children", "prior", "terminal", "state", "next_free",
           "free_list", "free_top")


@dataclasses.dataclass(frozen=True)
class TreeArena:
    """Flat SoA search tree (see module docstring for the plane layout)."""

    visits: Any
    value: Any
    vloss: Any
    unobs: Any
    parent: Any
    action: Any
    children: Any
    prior: Any
    terminal: Any
    state: Any
    next_free: Any
    free_list: Any
    free_top: Any

    # -- shape helpers (static ints, safe inside jit) -----------------------
    @property
    def max_nodes(self) -> int:
        return self.children.shape[-2]

    @property
    def num_actions(self) -> int:
        return self.children.shape[-1]

    def replace(self, **updates) -> "TreeArena":
        return dataclasses.replace(self, **updates)

    # -- deprecated dict-style access ---------------------------------------
    def __getitem__(self, key: str):
        if key not in _FIELDS:
            raise KeyError(key)
        warnings.warn(
            f"dict-style tree[{key!r}] access is deprecated; the tree is a "
            f"typed TreeArena now — use tree.{key} (repro.core.arena)",
            DeprecationWarning, stacklevel=2)
        return getattr(self, key)


jax.tree_util.register_pytree_node(
    TreeArena,
    lambda t: (tuple(getattr(t, f) for f in _FIELDS), None),
    lambda _, c: TreeArena(*c),
)


def init_arena(root_state, num_actions: int, max_nodes: int,
               root_terminal=False) -> TreeArena:
    """Fresh arena: root at row 0, every other row unallocated."""
    a = num_actions
    with jax.named_scope(scopes.NODE_STATE):
        state = jax.tree_util.tree_map(
            lambda x: jnp.zeros((max_nodes,) + jnp.shape(x),
                                jnp.asarray(x).dtype).at[ROOT].set(x),
            root_state)
    return TreeArena(
        visits=jnp.zeros((max_nodes,), jnp.int32),
        value=jnp.zeros((max_nodes,), jnp.float32),
        vloss=jnp.zeros((max_nodes,), jnp.int32),
        unobs=jnp.zeros((max_nodes,), jnp.int32),
        parent=jnp.full((max_nodes,), UNEXPANDED, jnp.int32),
        action=jnp.full((max_nodes,), UNEXPANDED, jnp.int32),
        children=jnp.full((max_nodes, a), UNEXPANDED, jnp.int32),
        prior=jnp.full((max_nodes, a), 1.0 / a, jnp.float32),
        terminal=jnp.zeros((max_nodes,), bool)
        .at[ROOT].set(jnp.asarray(root_terminal, bool)),
        state=state,
        next_free=jnp.asarray(1, jnp.int32),
        free_list=jnp.zeros((max_nodes,), jnp.int32),
        free_top=jnp.asarray(0, jnp.int32),
    )


def live_mask(arena: TreeArena):
    """[N] bool — row is allocated (root, or has a parent)."""
    n = arena.max_nodes
    return (jnp.arange(n) == ROOT) | (arena.parent >= 0)


def capacity_left(arena: TreeArena):
    """Number of rows still allocatable (stack depth + untouched tail)."""
    return arena.free_top + (arena.max_nodes - arena.next_free)


def can_alloc(arena: TreeArena):
    return capacity_left(arena) > 0


def alloc(arena: TreeArena, take=True):
    """Allocate one row: ``(arena, row, ok)``.

    Pops the free-list LIFO first, else bumps ``next_free``.  ``ok`` is
    False (and ``row`` is the out-of-bounds sentinel ``max_nodes``, so
    ``mode="drop"`` scatters are no-ops) when ``take`` is False or the
    arena is full.  The caller writes the row's planes (parent/children/
    state/...) — ``alloc`` only moves the bookkeeping.
    """
    n = arena.max_nodes
    take = jnp.asarray(take, bool)
    ok = take & can_alloc(arena)
    use_stack = ok & (arena.free_top > 0)
    stack_row = arena.free_list[jnp.maximum(arena.free_top - 1, 0)]
    row = jnp.where(use_stack, stack_row, arena.next_free)
    row = jnp.where(ok, row, n).astype(jnp.int32)
    arena = arena.replace(
        next_free=arena.next_free + (ok & ~use_stack).astype(jnp.int32),
        free_top=arena.free_top - use_stack.astype(jnp.int32))
    return arena, row, ok


def release(arena: TreeArena, rows, mask=True):
    """Push rows onto the free-list and reset their planes.

    ``rows`` [K] i32 with ``mask`` [K] bool selecting which entries are
    real.  Contract: masked rows must be live, non-root, and distinct —
    releasing the root or double-releasing is a caller bug (not checked
    on-device).  After release the rows read as unallocated: parent = -1,
    children all UNEXPANDED, uniform prior, zeroed stats/state.
    """
    n, a = arena.max_nodes, arena.num_actions
    rows = jnp.atleast_1d(jnp.asarray(rows, jnp.int32))
    k = rows.shape[0]
    mask = jnp.broadcast_to(jnp.asarray(mask, bool), (k,))
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    pos = jnp.where(mask, arena.free_top + rank, n)
    widx = jnp.where(mask, rows, n)
    zeros_k = jnp.zeros((k,), jnp.int32)
    state = jax.tree_util.tree_map(
        lambda buf: buf.at[widx].set(
            jnp.zeros((k,) + buf.shape[1:], buf.dtype), mode="drop"),
        arena.state)
    return arena.replace(
        visits=arena.visits.at[widx].set(zeros_k, mode="drop"),
        value=arena.value.at[widx].set(jnp.zeros((k,)), mode="drop"),
        vloss=arena.vloss.at[widx].set(zeros_k, mode="drop"),
        unobs=arena.unobs.at[widx].set(zeros_k, mode="drop"),
        parent=arena.parent.at[widx].set(zeros_k + UNEXPANDED, mode="drop"),
        action=arena.action.at[widx].set(zeros_k + UNEXPANDED, mode="drop"),
        children=arena.children.at[widx].set(
            jnp.full((k, a), UNEXPANDED, jnp.int32), mode="drop"),
        prior=arena.prior.at[widx].set(
            jnp.full((k, a), 1.0 / a, jnp.float32), mode="drop"),
        terminal=arena.terminal.at[widx].set(
            jnp.zeros((k,), bool), mode="drop"),
        state=state,
        free_list=arena.free_list.at[pos].set(rows, mode="drop"),
        free_top=arena.free_top + mask.sum().astype(jnp.int32),
    )


def compact(arena: TreeArena, keep, new_root=ROOT) -> TreeArena:
    """Dense renumbering: kept rows pack to the front, ``new_root`` -> row 0.

    ``keep`` [N] bool (``new_root`` is kept implicitly); other kept rows
    keep their relative order at rows 1..n_live-1.  Child/parent indices
    are remapped; pointers at dropped rows become UNEXPANDED.  The free
    bookkeeping resets: ``next_free = n_live``, empty stack — compaction IS
    the recycling step, every dropped row is allocatable again.
    """
    n = arena.max_nodes
    idx = jnp.arange(n)
    new_root = jnp.asarray(new_root, jnp.int32)
    is_nr = idx == new_root
    keep = jnp.asarray(keep, bool) | is_nr
    others = keep & ~is_nr
    newidx = jnp.where(is_nr, 0, jnp.cumsum(others.astype(jnp.int32)))
    n_live = 1 + others.sum().astype(jnp.int32)
    # src[j] = old index of the row that lands at j (j < n_live)
    src = jnp.zeros((n,), jnp.int32).at[
        jnp.where(keep, newidx, n)].set(idx.astype(jnp.int32), mode="drop")
    dst_live = idx < n_live
    remap = jnp.where(keep, newidx, UNEXPANDED).astype(jnp.int32)

    def gather(plane, fill):
        out = plane[src]
        fill = jnp.asarray(fill, out.dtype)
        return jnp.where(jnp.reshape(dst_live, (n,) + (1,) * (out.ndim - 1)),
                         out, fill)

    ch = gather(arena.children, UNEXPANDED)
    ch = jnp.where(ch >= 0, remap[jnp.maximum(ch, 0)], UNEXPANDED)
    pr = gather(arena.parent, UNEXPANDED)
    pr = jnp.where(pr >= 0, remap[jnp.maximum(pr, 0)], UNEXPANDED)
    pr = pr.at[ROOT].set(UNEXPANDED)
    with jax.named_scope(scopes.NODE_STATE):
        state = jax.tree_util.tree_map(lambda p: gather(p, 0), arena.state)
    return arena.replace(
        visits=gather(arena.visits, 0),
        value=gather(arena.value, 0.0),
        vloss=gather(arena.vloss, 0),
        unobs=gather(arena.unobs, 0),
        parent=pr,
        action=gather(arena.action, UNEXPANDED).at[ROOT].set(UNEXPANDED),
        children=ch,
        prior=gather(arena.prior, 1.0 / arena.num_actions),
        terminal=gather(arena.terminal, False),
        state=state,
        next_free=n_live,
        free_list=jnp.zeros((n,), jnp.int32),
        free_top=jnp.asarray(0, jnp.int32),
    )


def reroot_ok(arena: TreeArena, action):
    """True when the committed child exists — rerooting onto it keeps a
    non-trivial subtree.  Callers gate on this; ``reroot`` with a missing
    child degrades to compacting the whole live tree under the old root."""
    return arena.children[ROOT, jnp.asarray(action, jnp.int32)] >= 0


def reroot(arena: TreeArena, action) -> TreeArena:
    """Promote root child ``action`` to row 0 and recycle everything else.

    Reachability from the new root is computed with parent-pointer doubling
    (ceil(log2 N) + 1 rounds of ``reach |= reach[link]; link = link[link]``),
    then ``compact`` renumbers the subtree densely — ``next_free`` falls to
    the subtree size, so long request lifetimes stay bounded by the live
    tree, not by cumulative search history (the §14 recycling contract).
    """
    n = arena.max_nodes
    child = arena.children[ROOT, jnp.asarray(action, jnp.int32)]
    nr = jnp.where(child >= 0, child, ROOT).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    link = jnp.where(arena.parent >= 0, arena.parent, idx)

    def body(_, c):
        reach, link = c
        return reach | reach[link], link[link]

    rounds = int(math.ceil(math.log2(max(n, 2)))) + 1
    reach, _ = jax.lax.fori_loop(0, rounds, body, (idx == nr, link))
    return compact(arena, reach & live_mask(arena), nr)


def arena_stats(arena: TreeArena) -> Dict[str, Any]:
    """Device-side occupancy summary — no host sync, safe inside jit."""
    return {
        "live": live_mask(arena).sum().astype(jnp.int32),
        "next_free": arena.next_free,
        "free_top": arena.free_top,
        "capacity_left": capacity_left(arena).astype(jnp.int32),
    }
