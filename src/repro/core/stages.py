"""The four MCTS operation-level tasks (OLT) as pure stage functions.

Paper §V-A: Select / Expand / Playout / Backup, with hard OLD dependencies
S→E→P→B inside one trajectory and soft ILD between trajectories.  Each stage
here is a pure function (tree, inputs) -> (tree, outputs) — the tree is the
typed ``core.arena.TreeArena`` — so the pipeline scheduler can compose them
over in-flight waves.

Serial stages (E, B) process a wave's lanes sequentially (scan) — matching
the paper's serial pipeline stages.  The Playout stage is fully parallel
(vmap) — the paper's replicated playout stage (Fig. 5).

Kernel/selection knobs (DESIGN.md §11/§14) — one consolidated pair on
``SearchParams``, threaded down from ``SearchConfig``:

* ``kernels`` — "auto" | "pallas" | "ref": which implementation backs the
  accelerated paths ("auto" resolves to "pallas" on TPU, "ref" elsewhere).
  The per-level UCT kernel cannot score PUCT, so ``resolved_uct_kernels``
  reports "ref" there.
  The old boolean ``use_pallas`` is accepted and forwarded under a
  ``DeprecationWarning``.
* ``vl_mode`` — in-flight decorrelation statistics (DESIGN.md §15):
    - "loss" — classic virtual loss: one ``vloss`` plane, added to N and
      subtracted (×``vl_weight``) from W, so Q is pessimistically corrupted
      while playouts are in flight (the historical default);
    - "wu"   — WU-UCT (arXiv 1810.11755): a separate ``unobs`` plane O that
      widens only the exploration term; Q = W/max(N,1) from completed
      statistics only.  The non-active plane stays all-zeros.
* ``wave_select`` — Select-stage iteration order:
    - "scan"     — lane-major: lane i+1 descends after lane i, seeing its
      virtual loss at every level (the original serial Select stage);
    - "lockstep" — depth-major: all lanes descend together, one batched
      ``[lanes, A]`` UCT argmax per tree level;
    - "mega"     — the fused select→expand→backup wave
      (``kernels/search_wave``): the whole lockstep descent plus the
      structural expand (and the pipeline tick's backup) in one launch
      against the arena planes, instead of a launch per tree level.
      Bit-for-bit equal to "lockstep" at ``lanes == 1``.
    - "auto"     — "mega" when the resolved kernels are Pallas, else "scan"
      (preserving the historical CPU default).
* ``level_assign`` — within-level lane assignment for the depth-major paths
  (lockstep/mega; DESIGN.md §16): "independent" scores every lane against an
  identical board (co-located lanes stack), "running" threads a
  running-assignment scan through the batched level pass so lane k sees
  lanes 0..k-1's same-level picks and co-located lanes spread.  No-op for
  "scan" (lane-major already serializes whole descents).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import scopes, uct
from repro.core.arena import alloc as arena_alloc
from repro.core.tree import ROOT, UNEXPANDED, Tree, get_state, max_nodes


WAVE_SELECT_MODES = ("auto", "scan", "lockstep", "mega")
KERNEL_MODES = ("auto", "pallas", "ref")
LEVEL_ASSIGN_MODES = ("independent", "running")


@dataclasses.dataclass(frozen=True)
class SearchParams:
    cp: float = 1.414
    vl_weight: float = 1.0
    max_depth: int = 32
    puct: bool = False
    # In-flight decorrelation statistics: "loss" (virtual loss, default —
    # unchanged behaviour) or "wu" (WU-UCT unobserved counts, DESIGN §15).
    vl_mode: str = "loss"
    # Which implementation backs the accelerated paths ("auto" -> "pallas"
    # on TPU, "ref" elsewhere).  One knob for the per-level UCT kernel and
    # the fused search-wave megakernel alike.
    kernels: str = "auto"
    # Select-stage iteration order (see module docstring).
    wave_select: str = "auto"
    # Within-level lane assignment for the depth-major paths (DESIGN.md §16):
    # "independent" — co-located lanes score an identical board and may stack
    # on one child until Expand fans them out (the historical behaviour);
    # "running"     — a running-assignment scan inside the batched level
    # pass: lane k scores with the in-flight plane already incremented by
    # lanes 0..k-1's picks at that same level, so one launch per level still
    # serves the whole wave but co-located lanes spread over viable
    # children.  A documented no-op for wave_select="scan" (the lane-major
    # descent already sees earlier lanes' counts at every level).
    level_assign: str = "independent"
    # DEPRECATED: the old boolean kernel switch.  Accepted and forwarded
    # into ``kernels`` ("pallas"/"ref") when ``kernels`` is left at "auto".
    use_pallas: Optional[bool] = None

    def __post_init__(self):
        if self.vl_mode not in uct.VL_MODES:
            raise ValueError(
                f"vl_mode must be one of {uct.VL_MODES}, got {self.vl_mode!r}")
        if self.level_assign not in LEVEL_ASSIGN_MODES:
            raise ValueError(
                f"level_assign must be one of {LEVEL_ASSIGN_MODES}, "
                f"got {self.level_assign!r}")
        if self.use_pallas is not None:
            warnings.warn(
                "SearchParams.use_pallas is deprecated; use "
                "kernels='pallas'|'ref' (forwarding "
                f"use_pallas={self.use_pallas!r})", DeprecationWarning,
                stacklevel=2)
            if self.kernels == "auto":
                object.__setattr__(
                    self, "kernels", "pallas" if self.use_pallas else "ref")

    @property
    def wu(self) -> bool:
        return self.vl_mode == "wu"

    @property
    def running(self) -> bool:
        return self.level_assign == "running"

    @property
    def path_len(self) -> int:
        return self.max_depth + 2          # root .. deepest leaf + expanded child

    @property
    def resolved_kernels(self) -> str:
        if self.kernels not in KERNEL_MODES:
            raise ValueError(
                f"kernels must be one of {KERNEL_MODES}, got {self.kernels!r}")
        if self.kernels == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "ref"
        return self.kernels

    @property
    def resolved_uct_kernels(self) -> str:
        """What scores the per-level descents (scan, lockstep, and the
        single-root strategies): ``resolved_kernels``, except "ref" under
        PUCT — the ``uct_select`` kernel takes no priors.  The fused
        megakernel scores PUCT in-kernel either way."""
        return "ref" if self.puct else self.resolved_kernels

    @property
    def pallas_enabled(self) -> bool:
        return self.resolved_uct_kernels == "pallas"

    @property
    def resolved_wave_select(self) -> str:
        if self.wave_select not in WAVE_SELECT_MODES:
            raise ValueError(
                f"wave_select must be one of {WAVE_SELECT_MODES}, "
                f"got {self.wave_select!r}")
        if self.wave_select == "auto":
            return "mega" if self.resolved_kernels == "pallas" else "scan"
        return self.wave_select


@jax.named_scope(scopes.TREE)
def empty_selection(sp: SearchParams, lanes: int):
    return {
        "path": jnp.full((lanes, sp.path_len), UNEXPANDED, jnp.int32),
        "leaf": jnp.zeros((lanes,), jnp.int32),
        "depth": jnp.zeros((lanes,), jnp.int32),
        "valid": jnp.zeros((lanes,), bool),
        "dup": jnp.zeros((lanes,), bool),
        "dup_within": jnp.zeros((lanes,), bool),
        "dup_cross": jnp.zeros((lanes,), bool),
    }


@jax.named_scope(scopes.NODE_STATE)
def empty_expansion(sp: SearchParams, lanes: int, domain):
    state = jax.tree_util.tree_map(
        lambda x: jnp.zeros((lanes,) + jnp.shape(x), jnp.asarray(x).dtype),
        domain.root_state())
    return {
        "path": jnp.full((lanes, sp.path_len), UNEXPANDED, jnp.int32),
        "node": jnp.zeros((lanes,), jnp.int32),
        "is_new": jnp.zeros((lanes,), bool),
        "state": state,
        "valid": jnp.zeros((lanes,), bool),
    }


@jax.named_scope(scopes.TREE)
def empty_playout(sp: SearchParams, lanes: int, num_actions: int):
    return {
        "path": jnp.full((lanes, sp.path_len), UNEXPANDED, jnp.int32),
        "node": jnp.zeros((lanes,), jnp.int32),
        "is_new": jnp.zeros((lanes,), bool),
        "value": jnp.zeros((lanes,), jnp.float32),
        "priors": jnp.zeros((lanes, num_actions), jnp.float32),
        "valid": jnp.zeros((lanes,), bool),
    }


def infl_plane(tree: Tree, sp: SearchParams):
    """The mode's in-flight counter plane: ``unobs`` ("wu") / ``vloss``
    ("loss").  Static selection — the other plane stays all-zeros."""
    return tree.unobs if sp.wu else tree.vloss


def with_infl(tree: Tree, sp: SearchParams, plane) -> Tree:
    """Write ``plane`` back to the mode's in-flight field."""
    return tree.replace(unobs=plane) if sp.wu else tree.replace(vloss=plane)


# ---------------------------------------------------------------------------
# SELECT — UCT descent with in-flight decorrelation (serial stage)
# ---------------------------------------------------------------------------
@jax.named_scope(scopes.TREE)
def select_one(tree: Tree, sp: SearchParams, valid):
    """Descend from the root; returns (tree+in-flight, trajectory dict)."""
    def cond(c):
        node, depth, _ = c
        fully = (tree.children[node] >= 0).all()
        return fully & ~tree.terminal[node] & (depth < sp.max_depth)

    infl = infl_plane(tree, sp)

    def body(c):
        node, depth, path = c
        ch = tree.children[node]
        idx = jnp.maximum(ch, 0)
        a = uct.uct_argmax(
            tree.visits[idx], tree.value[idx], infl[idx],
            tree.visits[node] + infl[node], sp.cp,
            vl_weight=sp.vl_weight, prior=tree.prior[node],
            puct=sp.puct, valid=ch >= 0, use_pallas=sp.pallas_enabled,
            child_o=infl[idx], vl_mode=sp.vl_mode)
        nxt = ch[a]
        path = path.at[depth + 1].set(nxt)
        return nxt, depth + 1, path

    path0 = jnp.full((sp.path_len,), UNEXPANDED, jnp.int32).at[0].set(ROOT)
    leaf, depth, path = jax.lax.while_loop(cond, body, (jnp.int32(ROOT), jnp.int32(0), path0))
    dup = (infl[leaf] > 0) & valid
    mask = (path >= 0) & valid
    tree = with_infl(
        tree, sp,
        infl.at[jnp.maximum(path, 0)].add(mask.astype(jnp.int32)))
    sel = {"path": jnp.where(valid, path, UNEXPANDED), "leaf": leaf,
           "depth": depth, "valid": valid, "dup": dup}
    return tree, sel


@jax.named_scope(scopes.TREE)
def select_wave_scan(tree: Tree, sp: SearchParams, lanes: int, valid):
    """Lane-major Select: lane i+1 sees lane i's virtual loss (paper Fig. 5:
    one serial Select stage feeding multiple playout stages)."""
    infl_pre = infl_plane(tree, sp)   # in-flight counts before this wave

    def body(tr, _):
        tr, sel = select_one(tr, sp, valid)
        return tr, sel

    tree, sels = jax.lax.scan(body, tree, None, length=lanes)
    # split the dup event (a leaf that already had in-flight playouts) into
    # its two sources: an earlier unfinished wave (cross) vs a lower-numbered
    # valid lane of THIS wave (within).  Only a same-wave lane's own leaf can
    # carry within-wave in-flight counts — interior path nodes are fully
    # expanded and can never be another lane's leaf — so dup == within|cross.
    leaf, v = sels["leaf"], sels["valid"]
    sels["dup_within"] = (jnp.tril(leaf[:, None] == leaf[None, :], k=-1)
                          & v[None, :]).any(axis=1) & v
    sels["dup_cross"] = (infl_pre[leaf] > 0) & v
    return tree, sels


@jax.named_scope(scopes.TREE)
def select_wave_fused(tree: Tree, sp: SearchParams, lanes: int, valid):
    """Depth-major lockstep Select (DESIGN.md §11): every loop iteration is
    one tree level, scoring all active lanes' children with a single batched
    ``[lanes, A]`` UCT argmax — one ``uct_argmax_tiles`` launch with
    ``r = lanes`` under Pallas kernels, instead of ``lanes`` single-row
    calls per level.

    The in-flight count (``vloss`` in "loss" mode, ``unobs`` in "wu" mode)
    is applied per level: every selected child gets +1 before the next
    level's scores are computed, so deeper levels see the whole wave's
    in-flight counts (tree-parallel decorrelation).  How lanes at the SAME
    level see each other is ``sp.level_assign`` (DESIGN.md §16):
    "independent" scores the whole board at once (co-located lanes pick
    identically until Expand fans them out); "running" assigns lanes in
    order within the level — lane k's board row carries the picks of lanes
    0..k-1 sharing its parent, so co-located lanes spread over viable
    children while one batched call per level still serves the wave.  A
    lane's own count on its current node is excluded from ``parent_n``,
    which makes the descent bit-for-bit identical to ``select_wave_scan``
    at ``lanes == 1`` in either assignment mode (the running delta is
    identically zero for a single lane).
    Finished/invalid lanes mask out via the argmax's ``valid`` lanes.
    """
    valid = jnp.broadcast_to(jnp.asarray(valid, bool), (lanes,))
    nmax = max_nodes(tree)
    rows = jnp.arange(lanes)
    infl_pre = infl_plane(tree, sp)   # in-flight counts before this wave

    def lane_active(node, depth):
        fully = (tree.children[node] >= 0).all(axis=-1)
        return fully & ~tree.terminal[node] & (depth < sp.max_depth)

    # root in-flight count up front: the root is on every valid lane's path
    infl0 = infl_pre.at[ROOT].add(valid.sum().astype(jnp.int32))
    node0 = jnp.full((lanes,), ROOT, jnp.int32)
    depth0 = jnp.zeros((lanes,), jnp.int32)
    path0 = jnp.full((lanes, sp.path_len), UNEXPANDED, jnp.int32) \
        .at[:, 0].set(ROOT)
    active0 = valid & lane_active(node0, depth0)

    def cond(c):
        return c[4].any()

    def body(c):
        infl, node, depth, path, active = c
        ch = tree.children[node]                           # [lanes, A]
        idx = jnp.maximum(ch, 0)
        own = active.astype(jnp.int32)         # own in-flight count
        pn = tree.visits[node] + infl[node] - own
        kw = dict(vl_weight=sp.vl_weight, prior=tree.prior[node],
                  puct=sp.puct, valid=(ch >= 0) & active[:, None],
                  use_pallas=sp.pallas_enabled,
                  child_o=infl[idx], vl_mode=sp.vl_mode)
        if sp.running:    # lane k's row sees lanes 0..k-1's picks (§16)
            a = uct.uct_argmax_running(
                tree.visits[idx], tree.value[idx], infl[idx], pn, node,
                sp.cp, **kw)
        else:
            a = uct.uct_argmax(
                tree.visits[idx], tree.value[idx], infl[idx], pn, sp.cp,
                **kw)
        nxt = ch[rows, a]
        col = jnp.where(active, depth + 1, sp.path_len)    # OOB -> dropped
        path = path.at[rows, col].set(nxt, mode="drop")
        infl = infl.at[jnp.where(active, nxt, nmax)].add(1, mode="drop")
        node = jnp.where(active, nxt, node)
        depth = depth + own
        active = active & lane_active(node, depth)
        return infl, node, depth, path, active

    infl, leaf, depth, path, _ = jax.lax.while_loop(
        cond, body, (infl0, node0, depth0, path0, active0))
    tree = with_infl(tree, sp, infl)
    # same meaning as the scan path's dup: the lane's leaf was already
    # in-flight when it arrived — split into its two sources: an earlier
    # unfinished wave (cross), or a lower-numbered lane of this wave
    # (within — the stacking that level_assign="running" removes when the
    # leaf's parent still has viable siblings)
    dup_within = (jnp.tril(leaf[:, None] == leaf[None, :], k=-1)
                  .any(axis=1)) & valid
    dup_cross = (infl_pre[leaf] > 0) & valid
    sel = {"path": jnp.where(valid[:, None], path, UNEXPANDED),
           "leaf": leaf, "depth": depth, "valid": valid,
           "dup": dup_within | dup_cross,
           "dup_within": dup_within, "dup_cross": dup_cross}
    return tree, sel


def select_wave(tree: Tree, sp: SearchParams, lanes: int, valid):
    """Dispatch on ``sp.resolved_wave_select`` (static at trace time).
    "mega" at this stage-level granularity descends exactly like
    "lockstep" — the fusion with expand/backup happens one level up
    (``mega_round`` / ``mega_tick``)."""
    if sp.resolved_wave_select in ("lockstep", "mega"):
        return select_wave_fused(tree, sp, lanes, valid)
    return select_wave_scan(tree, sp, lanes, valid)


# ---------------------------------------------------------------------------
# EXPAND — allocate one child per trajectory (serial stage)
# ---------------------------------------------------------------------------
@jax.named_scope(scopes.TREE)
def expand_one(tree: Tree, domain, sp: SearchParams, sel):
    leaf, depth, valid = sel["leaf"], sel["depth"], sel["valid"]
    row = tree.children[leaf]
    has_slot = (row == UNEXPANDED).any()
    can_try = valid & has_slot & ~tree.terminal[leaf]
    tree, new, can = arena_alloc(tree, can_try)
    a = jnp.argmax(row == UNEXPANDED).astype(jnp.int32)
    with jax.named_scope(scopes.NODE_STATE):
        parent_state = get_state(tree, leaf)
    with jax.named_scope(scopes.EXPAND):
        child_state = domain.step(parent_state, a)
        term = domain.is_terminal(child_state)

    nmax = max_nodes(tree)
    with jax.named_scope(scopes.NODE_STATE):
        state = jax.tree_util.tree_map(
            lambda buf, s: buf.at[new].set(s, mode="drop"),
            tree.state, child_state)
    infl_upd = {("unobs" if sp.wu else "vloss"):
                infl_plane(tree, sp).at[new].add(1, mode="drop")}
    tree = tree.replace(
        children=tree.children.at[
            jnp.where(can, leaf, nmax), a].set(new, mode="drop"),
        parent=tree.parent.at[new].set(leaf, mode="drop"),
        action=tree.action.at[new].set(a, mode="drop"),
        terminal=tree.terminal.at[new].set(term, mode="drop"),
        state=state, **infl_upd)

    node = jnp.where(can, new, leaf)
    path = sel["path"].at[depth + 1].set(jnp.where(can, new, UNEXPANDED))
    with jax.named_scope(scopes.NODE_STATE):
        state = jax.tree_util.tree_map(
            lambda s_par, s_ch: jnp.where(
                jnp.reshape(can, (1,) * jnp.ndim(s_ch)), s_ch, s_par)
            if jnp.ndim(s_ch) else jnp.where(can, s_ch, s_par),
            parent_state, child_state)
    return tree, {"path": path, "node": node, "is_new": can, "state": state,
                  "valid": valid}


@jax.named_scope(scopes.TREE)
def expand_wave(tree: Tree, domain, sp: SearchParams, sels):
    def body(tr, sel):
        tr, exp = expand_one(tr, domain, sp, sel)
        return tr, exp

    tree, exps = jax.lax.scan(body, tree, sels)
    return tree, exps


# ---------------------------------------------------------------------------
# PLAYOUT — parallel stage (vmap over lanes; paper Fig. 5 replicated stage)
# ---------------------------------------------------------------------------
@jax.named_scope(scopes.PLAYOUT)
def playout_wave(domain, sp: SearchParams, exp, rng):
    lanes = exp["node"].shape[0]
    rngs = jax.random.split(rng, lanes)
    values = jax.vmap(domain.playout)(exp["state"], rngs)
    if hasattr(domain, "priors"):
        priors = jax.vmap(domain.priors)(exp["state"])
    else:
        a = domain.num_actions
        priors = jnp.full((lanes, a), 1.0 / a, jnp.float32)
    return {"path": exp["path"], "node": exp["node"], "is_new": exp["is_new"],
            "value": values.astype(jnp.float32), "priors": priors,
            "valid": exp["valid"]}


# ---------------------------------------------------------------------------
# BACKUP — scatter-add along paths (commutative => order-independent)
# ---------------------------------------------------------------------------
@jax.named_scope(scopes.TREE)
def backup_wave(tree: Tree, po, sp: Optional[SearchParams] = None):
    """Scatter-add N/W along paths and drain the mode's in-flight plane.
    ``sp=None`` keeps the historical signature and means "loss" mode."""
    paths = po["path"]                                     # [L, P]
    valid = po["valid"]
    mask = (paths >= 0) & valid[:, None]
    idx = jnp.maximum(paths, 0).reshape(-1)
    m = mask.reshape(-1)
    vals = jnp.broadcast_to(po["value"][:, None], paths.shape).reshape(-1)
    # write priors for freshly created nodes
    widx = jnp.where(po["is_new"] & valid, po["node"], max_nodes(tree))
    wu = sp is not None and sp.wu
    infl = (tree.unobs if wu else tree.vloss).at[idx].add(-m.astype(jnp.int32))
    return tree.replace(
        visits=tree.visits.at[idx].add(m.astype(jnp.int32)),
        value=tree.value.at[idx].add(jnp.where(m, vals, 0.0)),
        prior=tree.prior.at[widx].set(po["priors"], mode="drop"),
        **{("unobs" if wu else "vloss"): infl})


# ---------------------------------------------------------------------------
# MEGA — fused select→expand(→backup) waves (kernels/search_wave, §14)
# ---------------------------------------------------------------------------
def mega_round(tree: Tree, domain, sp: SearchParams, lanes: int, valid, rng):
    """One tree-parallel round as two fused launches: [select→expand] +
    playout + [backup].  Replaces select_wave + expand_wave's
    scan-over-lanes with the fused wave; bit-for-bit equal to the lockstep
    path at ``lanes == 1``.  Returns (tree, sel)."""
    from repro.kernels.search_wave import ops as wave
    return wave.tree_round(tree, domain, sp, lanes, valid, rng)


def mega_tick(tree: Tree, domain, sp: SearchParams, lanes: int, wave_valid,
              buf_se, buf_ep, buf_pb, rng):
    """One pipeline tick as a single fused [backup→expand→select] launch
    plus the out-of-launch playout and expand-finish (domain model calls
    cannot run inside a kernel).  Returns (tree, new_se, new_ep, new_pb)."""
    from repro.kernels.search_wave import ops as wave
    return wave.pipeline_tick(tree, domain, sp, lanes, wave_valid,
                              buf_se, buf_ep, buf_pb, rng)
