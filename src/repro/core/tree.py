"""Search-tree API — thin wrappers over the typed ``core.arena.TreeArena``.

The tree used to be a raw ``Dict[str, Any]`` pytree; it is now the typed
SoA arena (``repro.core.arena``) with a free-list so rows are recycled
across a serving request's lifetime.  This module keeps the historical
entry points (``init_tree`` / ``get_state`` / ``reroot`` /
``warm_start_root`` / ``check_consistency``) as thin wrappers; dict-style
``tree["visits"]`` still works for one release via the arena's
``__getitem__`` deprecation shim.

API change (DESIGN.md §14): ``reroot`` now returns the rerooted *arena*
(the committed child promoted to row 0, abandoned siblings recycled) —
serving carries the whole subtree across tokens.  The old stat-compacting
behaviour survives as ``root_carry`` (the ``RootCarry`` warm-start path).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core.arena import (ROOT, UNEXPANDED, TreeArena, init_arena,
                              live_mask)
from repro.core.arena import reroot as _arena_reroot
from repro.core.arena import reroot_ok  # noqa: F401  (re-export)

Tree = TreeArena


@jax.named_scope(scopes.TREE)
def init_tree(domain, max_nodes: int) -> Tree:
    """Build the search tree for ``domain``.

    Starts cold (root = ``domain.root_state()``), then applies the optional
    cross-token warm-start hooks carried on the domain:

    * ``domain.root_warm``  — a ``RootCarry`` seeding the root's N/W/prior
      (statistic-level reuse, DESIGN.md §12);
    * ``domain.root_arena`` — a full carried ``TreeArena`` (same capacity)
      spliced in wholesale when ``domain.root_arena_alive`` (subtree-level
      reuse, DESIGN.md §14); when not alive the cold tree is used, making
      the empty carry bit-for-bit a cold search.
    """
    root_state = domain.root_state()
    tree = init_arena(root_state, domain.num_actions, max_nodes,
                      domain.is_terminal(root_state))
    warm = getattr(domain, "root_warm", None)
    if warm is not None:
        tree = warm_start_root(tree, warm)
    carried = getattr(domain, "root_arena", None)
    if carried is not None:
        alive = getattr(domain, "root_arena_alive", None)
        alive = jnp.asarray(True if alive is None else alive, bool)
        with jax.named_scope(scopes.NODE_STATE):
            tree = jax.tree_util.tree_map(
                lambda c, f: jnp.where(
                    jnp.reshape(alive, (1,) * jnp.ndim(f)), c, f),
                carried, tree)
    return tree


def empty_root_carry(num_actions: int) -> Dict[str, Any]:
    """The identity ``RootCarry``: warm-starting with it is bit-for-bit a
    cold search (zero visits, uniform prior — exactly ``init_tree``'s
    defaults), so freshly admitted serving slots just reset to this."""
    a = num_actions
    return {
        "visits": jnp.asarray(0, jnp.int32),
        "value": jnp.asarray(0.0, jnp.float32),
        "prior": jnp.full((a,), 1.0 / a, jnp.float32),
        "child_visits": jnp.zeros((a,), jnp.int32),
        "child_value": jnp.zeros((a,), jnp.float32),
    }


def root_carry(tree: Tree, action) -> Dict[str, Any]:
    """Compact the subtree under root child ``action`` into a ``RootCarry``
    (DESIGN.md §12): the chosen child's N/W, its stored prior row, and its
    children's N/W — the statistic-level warm start (``warm_start_root``).
    Unvisited slots fall back to the identity carry.  For full subtree
    reuse use ``reroot``, which keeps the whole arena."""
    a = num_actions(tree)
    c = tree.children[ROOT][action]
    has = c >= 0
    ci = jnp.maximum(c, 0)
    gch = tree.children[ci]                          # grandchildren [A]
    gvalid = (gch >= 0) & has
    gi = jnp.maximum(gch, 0)
    return {
        "visits": jnp.where(has, tree.visits[ci], 0).astype(jnp.int32),
        "value": jnp.where(has, tree.value[ci], 0.0).astype(jnp.float32),
        "prior": jnp.where(has, tree.prior[ci],
                           jnp.full((a,), 1.0 / a, jnp.float32)),
        "child_visits": jnp.where(gvalid, tree.visits[gi],
                                  0).astype(jnp.int32),
        "child_value": jnp.where(gvalid, tree.value[gi],
                                 0.0).astype(jnp.float32),
    }


@jax.named_scope(scopes.TREE)
def reroot(tree: Tree, action) -> Tree:
    """Promote root child ``action`` to the root and recycle the abandoned
    rows (``core.arena.reroot``).  Returns the rerooted arena — the next
    search's ready-made tree.  Note: carried ``terminal`` flags reflect the
    *previous* horizon; callers re-deriving the horizon (serving) refresh
    them against the new domain (DESIGN.md §14)."""
    return _arena_reroot(tree, action)


@jax.named_scope(scopes.TREE)
def warm_start_root(tree: Tree, carry: Dict[str, Any]) -> Tree:
    """Seed a fresh tree's root from a ``RootCarry`` (cross-token subtree
    reuse, DESIGN.md §12): root N/W start at the carried child's counts and
    the root prior blends the carried prior with the carried grandchild
    visit distribution — previously explored continuations start favoured
    (PUCT) instead of uniform.  ``warm_start_root(t, empty_root_carry(A))``
    is bit-for-bit the identity: ``(prior + 0) / (1 + 0) == prior``."""
    cv = carry["child_visits"].astype(jnp.float32)
    prior = (carry["prior"] + cv) / (1.0 + cv.sum())
    return tree.replace(
        visits=tree.visits.at[ROOT].set(carry["visits"].astype(jnp.int32)),
        value=tree.value.at[ROOT].set(carry["value"].astype(jnp.float32)),
        prior=tree.prior.at[ROOT].set(prior))


def max_nodes(tree: Tree) -> int:
    return tree.max_nodes


def num_actions(tree: Tree) -> int:
    return tree.num_actions


def get_state(tree: Tree, node):
    return jax.tree_util.tree_map(lambda x: x[node], tree.state)


def root_action_by_visits(tree: Tree):
    """Final move selection: most-visited root child (standard robust child)."""
    ch = tree.children[ROOT]
    n = jnp.where(ch >= 0, tree.visits[jnp.maximum(ch, 0)], -1)
    return jnp.argmax(n)


def root_child_stats(tree: Tree):
    ch = tree.children[ROOT]
    valid = ch >= 0
    idx = jnp.maximum(ch, 0)
    n = jnp.where(valid, tree.visits[idx], 0)
    w = jnp.where(valid, tree.value[idx], 0.0)
    return n, w, valid


def check_consistency(tree: Tree) -> Dict[str, Any]:
    """Invariant summary (tests): visit flow conservation, vloss drained,
    parent pointers live.  Fully device-side — 0-d bool/int arrays, no
    ``int()`` host round-trip, so it is safe to call inside traced code."""
    n = max_nodes(tree)
    idx = jnp.arange(n)
    alive = live_mask(tree)
    ok_vloss = (tree.vloss == 0).all()
    ok_unobs = (tree.unobs == 0).all()
    ch = tree.children[ROOT]
    child_sum = jnp.where(ch >= 0, tree.visits[jnp.maximum(ch, 0)], 0).sum()
    ok_flow = child_sum <= tree.visits[ROOT]
    nonroot = alive & (idx != ROOT)
    p = tree.parent
    ok_parent = jnp.where(
        nonroot,
        (p >= 0) & (p < n) & alive[jnp.clip(p, 0, n - 1)],
        True).all()
    return {"vloss_drained": ok_vloss, "unobs_drained": ok_unobs,
            "visit_flow": ok_flow, "parents_valid": ok_parent,
            "nodes": alive.sum()}
