"""Stage scopes, engine spans and root counters of MCTS serving.

* The compiled per-token program names its stages: all six scopes of
  ``repro.core.scopes`` appear in its ``op_name`` metadata on every select
  path, and every dot, sort, custom call, fusion and copy the program's own
  code emits maps to one, but for the search loop's plumbing.
* ``ServingEngine`` writes its host spans into a profile: admission,
  dispatch, sync and commit inside each step, admissions with their uid.
* The per-token program returns the root's visits and mean values per
  action, playouts and duplicates; the engine keeps them per committed
  token and as totals.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scopes
from repro.core.tree import root_child_stats
from repro.models.base import ModelConfig, get_family
from repro.search import search_batch
from repro.serving import (EngineConfig, MCTSDecodeConfig, Request,
                           ServingEngine, make_batched_searcher)
from repro.serving.mcts_decode import _domain, unpack

CFG = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32", ce_chunk=8, remat=False)
DCFG = MCTSDecodeConfig(num_actions=3, budget=6, lanes=2, search_depth=2,
                        rollout_len=1)
BUF_LEN = 8
HEAVY = ("dot", "sort", "custom-call", "fusion", "copy")
# what the search loop itself emits: its carries, tick counters and bounds
LOOP_PLUMBING = {"closed_call", "dynamic_update_slice", "dynamic_slice",
                 "add", "lt", "reduce_sum", "broadcast_in_dim", "select_n"}


@pytest.fixture(scope="module")
def params():
    return get_family(CFG).init(CFG, jax.random.key(0))


def _buf():
    buf = np.zeros((2, BUF_LEN), np.int32)
    buf[0, :3] = [1, 2, 3]
    buf[1, :2] = [4, 5]
    return buf, np.array([3, 2], np.int32)


def _instructions(hlo: str):
    """(opcode, op_name or None) of every instruction outside fused
    computations: the ones a device profile shows as ops."""
    out, fused = [], False
    for line in hlo.splitlines():
        if not line.startswith(" "):
            fused = line.startswith(("%fused", "%wrapped", "fused",
                                     "wrapped"))
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z\-]*)\(",
                     line)
        if m and not fused:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else None))
    return out


def _plumbing(op_name) -> bool:
    """Made by the compiler (no op_name, or an argument's name), the
    search loop's own bookkeeping, or vmap batching the search's outputs."""
    if op_name is None or not op_name.startswith("jit("):
        return True
    last = op_name.rsplit("/", 1)[-1]
    return (("/while/" in op_name and last in LOOP_PLUMBING)
            or op_name.endswith("vmap()/broadcast_in_dim"))


@pytest.mark.parametrize("stateful", (False, True),
                         ids=("cold", "splice+reuse"))
@pytest.mark.parametrize("wave_select", ("scan", "lockstep", "mega"))
def test_program_names_every_stage(params, monkeypatch, stateful,
                                   wave_select):
    """The engine's per-token program, lowered and compiled at the tiny
    size: the megakernel in interpret mode."""
    from repro.kernels.search_wave import kernel as K
    call = K._call
    monkeypatch.setattr(K, "_call", lambda *a: call(*a[:-1], True))
    dcfg = dataclasses.replace(
        DCFG, wave_select=wave_select, kv_splice=stateful,
        tree_reuse=stateful,
        kernels="pallas" if wave_select == "mega" else "ref")
    s = make_batched_searcher(CFG, params, dcfg, batch=2, mesh=False)
    args = [*_buf(), jax.random.key(0)]
    if stateful:
        args.append(s.init_carry(BUF_LEN))
    hlo = s.lower(*args).compile().as_text()
    insts = _instructions(hlo)
    found = {scopes.stage_of(op) for _, op in insts if op}
    assert set(scopes.STAGES) <= found
    loose = [(code, op) for code, op in insts
             if code.startswith(HEAVY) and not _plumbing(op)
             and scopes.stage_of(op) is None]
    assert not loose, loose[:10]
    heavy = [op for code, op in insts if code in ("dot", "sort")]
    assert heavy and all(scopes.stage_of(op) for op in heavy if op)


def test_stage_of_takes_the_innermost_scope():
    assert scopes.stage_of(
        "jit(step)/search.tree/search.expand/vmap(search.topk)/top_k") == (
        scopes.TOPK)
    assert scopes.stage_of(
        "jit(step)/vmap(search.root)/while/body/dot_general") == scopes.ROOT
    assert scopes.stage_of("jit(step)/search.treeish/add") is None
    assert scopes.stage_of("jit(step)/while/body/add") is None


def _serve(params, n, max_new, batch=2):
    eng = ServingEngine(CFG, params, EngineConfig(
        max_batch=batch, max_seq=16, decode="mcts", mcts=DCFG, mesh=False))
    reqs = [Request(uid=u, prompt=np.array([1 + u, 2, 3], np.int32),
                    max_new_tokens=max_new) for u in range(n)]
    for r in reqs:
        eng.submit(r)
    return eng, reqs


def test_engine_spans_nest_in_each_step(params, tmp_path):
    """Two engine steps under the profiler, read back from its
    ``.xplane.pb``: each phase inside a step, admissions with uid/slot."""
    from jax.profiler import ProfileData
    eng, _ = _serve(params, 6, 1)
    eng.step()                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    eng.step()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serving.")]
    steps = [(s, e) for n, s, e, _ in spans if n == "serving.step"]
    assert len(steps) == 2
    names = {n for n, *_ in spans}
    assert {"serving.admit", "serving.search", "serving.sync",
            "serving.commit"} <= names
    for n, s, e, _ in spans:
        if n != "serving.step":
            assert any(a <= s and e <= b for a, b in steps), n
    admits = [m for n, _, _, m in spans if n == "serving.admit"]
    assert admits and all({"uid", "slot"} <= set(m) for m in admits)


@pytest.mark.parametrize("splice", (False, True), ids=("cold", "splice"))
def test_counters_match_the_search_tree(params, splice):
    """The root's visits and mean values per action equal the root
    children of ``search_batch``'s tree for the same keys, bit for bit.
    The visits sum to the playouts completed but for lanes that stopped at
    the root, which only a duplicate does (another lane of its wave took
    the root's last unexpanded action)."""
    dcfg = dataclasses.replace(DCFG, kv_splice=splice)
    s = make_batched_searcher(CFG, params, dcfg, batch=2, mesh=False)
    buf, lens = _buf()
    rng = jax.random.key(7)
    if splice:
        carry = s.init_carry(BUF_LEN)
        for i in range(2):
            carry = s.admit(carry, i, buf[i], lens[i])
        out, _ = s.search(buf, lens, rng, carry)
    else:
        out = s.search(buf, lens, rng)
    toks, c = unpack(jax.device_get(out))
    assert (toks == np.asarray(s(buf, lens, rng) if not splice
                               else s.step(buf, lens, rng, carry)[0])).all()
    assert c["root_visits"].dtype == np.int32
    assert c["root_values"].dtype == np.float32
    assert c["root_visits"].shape == c["root_values"].shape == (2, 3)
    assert (c["playouts"] == DCFG.budget).all()
    visits = c["root_visits"].sum(-1)
    assert (visits <= c["playouts"]).all()
    clean = c["duplicates"] == 0
    np.testing.assert_array_equal(visits[clean], c["playouts"][clean])

    domains = [_domain(CFG, params, jnp.asarray(buf[i]), DCFG,
                       prompt_len=jnp.int32(lens[i])) for i in range(2)]
    cfg = dataclasses.replace(DCFG.search_config(), keep_tree=True)
    res = search_batch(domains, cfg, rng, mesh=False)
    n, w, _ = jax.vmap(root_child_stats)(res.tree)
    n, w = np.asarray(n), np.asarray(w)
    np.testing.assert_array_equal(np.asarray(res.tree.visits[:, 0]),
                                  c["playouts"])
    np.testing.assert_array_equal(c["root_visits"], n)
    want = np.where(n > 0, w / np.maximum(n, 1).astype(np.float32), 0.0)
    np.testing.assert_array_equal(c["root_values"], want.astype(np.float32))
    np.testing.assert_array_equal(c["duplicates"],
                                  np.asarray(res.stats["duplicates"]))


def test_engine_keeps_counters_per_committed_token(params):
    eng, reqs = _serve(params, 3, 3)
    eng.run_until_drained()
    total = 0
    for req in reqs:
        assert req.done and len(req.out_tokens) == 3
        assert len(req.root_visits) == len(req.root_values) == 3
        for v, q in zip(req.root_visits, req.root_values):
            assert v.shape == q.shape == (DCFG.num_actions,)
            assert 0 < v.sum() <= DCFG.budget
            assert (q[v == 0] == 0).all() and (q[v > 0] > 0).all()
            total += int(v.sum())
    snap = eng.stats.snapshot()
    assert snap["serving/playouts"] == 9 * DCFG.budget
    assert total <= snap["serving/playouts"]
    assert snap["serving/duplicates"] >= 0


def test_greedy_path_keeps_no_counters(params):
    eng = ServingEngine(CFG, params, EngineConfig(max_batch=2, max_seq=16))
    req = Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                  max_new_tokens=2)
    eng.submit(req)
    eng.run_until_drained()
    assert len(req.out_tokens) == 2 and req.root_visits == []
    assert eng.stats.snapshot()["serving/playouts"] == 0.0
