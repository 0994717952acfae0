"""Lockstep (depth-major) vs scan (lane-major) wave selection (DESIGN.md §11).

Contracts under test:

* ``wave_select="scan"`` is the pre-existing path, untouched — and at
  ``lanes == 1`` the lockstep path is bit-for-bit identical to it (the
  exact-parity escape hatch of ISSUE 5).
* At ``lanes > 1`` the two paths differ per seed (per-level vs per-lane
  virtual loss) but agree in distribution: aggregate root-visit fractions
  stay within tolerance and both recommend the same aggregate best action.
* Tree invariants (vloss drained, visit flow) hold for lockstep runs.
* The lockstep Select stage issues ONE batched ``[lanes, A]`` UCT call per
  tree level (the scan path issues single-row calls) — asserted via a
  trace-time hook on ``repro.core.uct.uct_argmax``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stages as S
from repro.core import uct
from repro.core.domains.pgame import PGameDomain, optimal_root_action
from repro.core.tree import check_consistency
from repro.search import SearchConfig, SearchParams, search

DOM = PGameDomain(num_actions=4, game_depth=6, binary_reward=False, seed=3)
METHODS = ("tree", "pipeline")


def _cfg(method, ws, lanes, budget, **kw):
    sp = SearchParams(cp=0.7, max_depth=6, wave_select=ws)
    return SearchConfig(method=method, budget=budget, lanes=lanes,
                        params=sp, **kw)


def _run(method, ws, lanes, budget=128, seed=0, **kw):
    cfg = _cfg(method, ws, lanes, budget, **kw)
    return jax.jit(lambda r: search(DOM, cfg, r))(jax.random.key(seed))


# ---------------------------------------------------------------------------
# knob resolution
# ---------------------------------------------------------------------------
def test_wave_select_resolution():
    assert SearchParams().resolved_wave_select == "scan"
    # deprecated boolean forwards into the consolidated kernels knob; with
    # Pallas kernels the auto wave_select is the fused megakernel (§14)
    with pytest.warns(DeprecationWarning):
        sp = SearchParams(use_pallas=True)
    assert sp.resolved_kernels == "pallas"
    assert sp.resolved_wave_select == "mega"
    with pytest.warns(DeprecationWarning):
        sp = SearchParams(wave_select="scan", use_pallas=True)
    assert sp.resolved_wave_select == "scan"
    assert SearchParams(wave_select="lockstep").resolved_wave_select == "lockstep"
    assert SearchParams(kernels="pallas").resolved_wave_select == "mega"
    assert SearchParams(kernels="ref").resolved_wave_select == "scan"
    # explicit kernels wins over the deprecated boolean
    with pytest.warns(DeprecationWarning):
        sp = SearchParams(kernels="ref", use_pallas=True)
    assert sp.resolved_kernels == "ref"
    with pytest.raises(ValueError, match="wave_select"):
        _ = SearchParams(wave_select="nope").resolved_wave_select
    with pytest.raises(ValueError, match="kernels"):
        _ = SearchParams(kernels="nope").resolved_kernels


def test_puct_reports_ref_uct_kernels():
    """The per-level UCT kernel takes no priors: under PUCT the reported
    resolution says "ref" while the megakernel stays Pallas, and asking the
    kernel for PUCT directly raises instead of degrading unseen."""
    sp = SearchParams(kernels="pallas", puct=True)
    assert sp.resolved_kernels == "pallas"
    assert sp.resolved_wave_select == "mega"
    assert sp.resolved_uct_kernels == "ref" and not sp.pallas_enabled
    assert SearchParams(kernels="pallas").resolved_uct_kernels == "pallas"
    ones = jnp.ones((2, 4))
    with pytest.raises(ValueError, match="PUCT"):
        uct.uct_argmax(ones, ones, ones, jnp.ones((2,)), 1.0, prior=ones,
                       puct=True, use_pallas=True)


# ---------------------------------------------------------------------------
# exact parity at lanes=1 (and scan reproduces the default path bit-for-bit)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("seed", (0, 1))
def test_lockstep_exact_parity_at_lanes1(method, seed):
    a = _run(method, "scan", 1, seed=seed)
    b = _run(method, "lockstep", 1, seed=seed)
    np.testing.assert_array_equal(np.asarray(a.action_visits),
                                  np.asarray(b.action_visits))
    np.testing.assert_array_equal(np.asarray(a.action_value),
                                  np.asarray(b.action_value))
    np.testing.assert_array_equal(np.asarray(a.tree["visits"]),
                                  np.asarray(b.tree["visits"]))
    np.testing.assert_array_equal(np.asarray(a.tree["children"]),
                                  np.asarray(b.tree["children"]))
    for k in a.stats:
        assert int(a.stats[k]) == int(b.stats[k]), k


@pytest.mark.parametrize("method", METHODS)
def test_scan_mode_is_the_default_path(method):
    """``wave_select="scan"`` and the default params produce identical
    results — the escape hatch IS the pre-PR behaviour."""
    a = _run(method, "auto", 4, seed=2)        # use_pallas=False -> scan
    b = _run(method, "scan", 4, seed=2)
    np.testing.assert_array_equal(np.asarray(a.action_visits),
                                  np.asarray(b.action_visits))
    np.testing.assert_array_equal(np.asarray(a.tree["visits"]),
                                  np.asarray(b.tree["visits"]))


# ---------------------------------------------------------------------------
# statistical parity + invariants at wave sizes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", METHODS)
def test_lockstep_statistical_parity(method):
    """Aggregate root-visit fractions of lockstep and scan agree within
    tolerance at a converged budget, and both point at the same aggregate
    best action (distribution-level equivalence, not per-seed equality)."""
    seeds, budget, lanes = 6, 512, 8
    agg = {}
    for ws in ("scan", "lockstep"):
        cfg = _cfg(method, ws, lanes, budget, keep_tree=False)
        fn = jax.jit(lambda r: search(DOM, cfg, r).action_visits)
        v = np.zeros(DOM.num_actions)
        for s in range(seeds):
            v += np.asarray(fn(jax.random.key(s)))
        agg[ws] = v / v.sum()
    l1 = float(np.abs(agg["scan"] - agg["lockstep"]).sum())
    assert l1 < 0.25, (agg, l1)
    assert int(np.argmax(agg["scan"])) == int(np.argmax(agg["lockstep"]))
    assert int(np.argmax(agg["lockstep"])) == optimal_root_action(DOM)


@pytest.mark.parametrize("method", METHODS)
def test_wu_lockstep_statistical_parity_with_scan(method):
    """WU-UCT in-flight statistics (vl_mode="wu", DESIGN.md §15) change the
    per-seed trajectories but not the distribution: aggregate root-visit
    fractions of wu-lockstep at lanes=8 agree with the scan baseline within
    tolerance, and both find the true optimum."""
    seeds, budget, lanes = 6, 512, 8
    agg = {}
    for name, ws, vm in (("scan", "scan", "loss"),
                         ("wu", "lockstep", "wu")):
        sp = SearchParams(cp=0.7, max_depth=6, wave_select=ws, vl_mode=vm)
        cfg = SearchConfig(method=method, budget=budget, lanes=lanes,
                           params=sp, keep_tree=False)
        fn = jax.jit(lambda r: search(DOM, cfg, r).action_visits)
        v = np.zeros(DOM.num_actions)
        for s in range(seeds):
            v += np.asarray(fn(jax.random.key(s)))
        agg[name] = v / v.sum()
    l1 = float(np.abs(agg["scan"] - agg["wu"]).sum())
    assert l1 < 0.25, (agg, l1)
    assert int(np.argmax(agg["wu"])) == int(np.argmax(agg["scan"]))
    assert int(np.argmax(agg["wu"])) == optimal_root_action(DOM)


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("lanes", (4, 8))
def test_lockstep_invariants(method, lanes):
    res = _run(method, "lockstep", lanes, budget=256)
    c = check_consistency(res.tree)
    assert c["vloss_drained"], c
    assert c["visit_flow"], c
    assert c["parents_valid"], c
    assert int(res.stats["playouts"]) == 256
    assert int(res.tree["visits"][0]) == 256


def test_lockstep_terminal_root_no_descent():
    """All-lanes-done edge: a root that is terminal (or unexpanded) ends the
    level loop immediately — every lane reports the root as its leaf."""
    dom = PGameDomain(num_actions=3, game_depth=0, seed=0)   # root terminal
    sp = SearchParams(cp=0.7, max_depth=4, wave_select="lockstep")
    from repro.core.tree import init_tree
    tree = init_tree(dom, 8)
    tree2, sel = S.select_wave(tree, sp, 4, jnp.asarray(True))
    assert np.asarray(sel["leaf"]).tolist() == [0, 0, 0, 0]
    assert np.asarray(sel["depth"]).tolist() == [0, 0, 0, 0]
    # root VL applied for every valid lane, nothing deeper
    assert int(tree2["vloss"][0]) == 4
    assert int(tree2["vloss"][1:].sum()) == 0


def test_lockstep_invalid_wave_leaves_tree_untouched():
    """A fully-masked wave (pipeline drain tick) must not write any VL."""
    sp = SearchParams(cp=0.7, max_depth=6, wave_select="lockstep")
    from repro.core.tree import init_tree
    tree = init_tree(DOM, 16)
    tree2, sel = S.select_wave(tree, sp, 4, jnp.asarray(False))
    assert int(tree2["vloss"].sum()) == 0
    assert not bool(np.asarray(sel["valid"]).any())
    assert bool((np.asarray(sel["path"]) == -1).all())


# ---------------------------------------------------------------------------
# the batched-launch contract: one [lanes, A] UCT call per tree level
# ---------------------------------------------------------------------------
def _spy_shapes(monkeypatch):
    shapes = []
    real = uct.uct_argmax

    def spy(child_n, *a, **kw):
        shapes.append(tuple(child_n.shape))
        return real(child_n, *a, **kw)

    monkeypatch.setattr(uct, "uct_argmax", spy)
    return shapes


def test_lockstep_issues_one_batched_call_per_level(monkeypatch):
    shapes = _spy_shapes(monkeypatch)
    cfg = _cfg("tree", "lockstep", 8, 64)
    jax.jit(lambda r: search(DOM, cfg, r).best_action)(jax.random.key(0))
    # the level loop has exactly ONE traced select call, batched over lanes
    assert shapes == [(8, DOM.num_actions)]


def test_scan_issues_single_row_calls(monkeypatch):
    shapes = _spy_shapes(monkeypatch)
    cfg = _cfg("tree", "scan", 8, 64)
    jax.jit(lambda r: search(DOM, cfg, r).best_action)(jax.random.key(0))
    # lane-major: the per-lane descent scores one node's children at a time
    assert shapes == [(DOM.num_actions,)]


# ---------------------------------------------------------------------------
# lockstep through the serving config
# ---------------------------------------------------------------------------
def test_mcts_decode_config_threads_wave_select():
    from repro.serving.mcts_decode import MCTSDecodeConfig
    scfg = MCTSDecodeConfig(wave_select="lockstep").search_config()
    assert scfg.params.resolved_wave_select == "lockstep"
    assert MCTSDecodeConfig().search_config().params.wave_select == "auto"


def test_mcts_decode_config_threads_vl_mode():
    from repro.serving.mcts_decode import MCTSDecodeConfig
    assert MCTSDecodeConfig(vl_mode="wu").search_config().params.vl_mode \
        == "wu"
    assert MCTSDecodeConfig().search_config().params.vl_mode == "loss"
