"""The per-token program's time by search stage, and the engine's spans."""
import gzip
import json
import pathlib

import pytest

import stage_profile
from chipbench import stages, tracing

FIXTURE = pathlib.Path(__file__).parent / "fixtures"

# a module in the compiled form: a fusion, a loop whose body holds a
# compiler-made copy and the loop's own counter, a layout in a shape, an
# argument copied, and a Pallas call
HLO = """HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(f32[4]{0} %param_0.1, f32[4]{0} %param_0.1)
}

%body.2 (p.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.2 = (s32[], f32[4]{0}) parameter(0)
  %gte.3 = s32[] get-tuple-element((s32[], f32[4]{0}) %p.2), index=0
  %gte.4 = f32[4]{0:T(128)S(1)} get-tuple-element((s32[], f32[4]{0}) %p.2), index=1
  %fusion.5 = f32[4]{0:T(128)} fusion(f32[4]{0} %gte.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/search.tree/search.playout/vmap(search.topk)/add"}
  %copy.6 = f32[4]{0} copy(f32[4]{0} %fusion.5)
  %add.7 = s32[] add(s32[] %gte.3, s32[] %gte.3), metadata={op_name="jit(step)/while/body/add"}
  ROOT %tuple.8 = (s32[], f32[4]{0}) tuple(s32[] %add.7, f32[4]{0} %copy.6)
}

%cond.9 (p.9: (s32[], f32[4])) -> pred[] {
  %p.9 = (s32[], f32[4]{0}) parameter(0)
  %gte.10 = s32[] get-tuple-element((s32[], f32[4]{0}) %p.9), index=0
  %constant.11 = s32[] constant(3)
  ROOT %compare.12 = pred[] compare(s32[] %gte.10, s32[] %constant.11), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main.13 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="buf"}
  %copy.14 = f32[4]{0} copy(f32[4]{0} %Arg_0.1), metadata={op_name="buf"}
  %sort.15 = f32[4]{0} sort(f32[4]{0} %copy.14), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(step)/vmap(search.root)/search.topk/sort"}
  %constant.16 = s32[] constant(0)
  %tuple.17 = (s32[], f32[4]{0}) tuple(s32[] %constant.16, f32[4]{0} %sort.15)
  %while.18 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tuple.17), condition=%cond.9, body=%body.2, metadata={op_name="jit(step)/while"}
  %gte.19 = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %while.18), index=1
  %copy.20 = f32[4]{0:T(128)S(1)} copy(f32[4]{0} %gte.19)
  %closed_call.21 = f32[4]{0} custom-call(f32[4]{0} %copy.20), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/search.tree/search.node_state/search_wave_bes/pallas_call"}
  ROOT %copy.22 = f32[4]{0} copy(f32[4]{0} %closed_call.21)
}
"""


def test_stage_of_innermost():
    assert stages.stage_of(
        "jit(step)/search.tree/search.expand/vmap(search.topk)/top_k") == (
        "search.topk")
    assert stages.stage_of("jit(step)/vmap(search.root)/dot") == (
        "search.root")
    assert stages.stage_of("jit(step)/search.treeish/add") is None
    assert stages.stage_of("jit(step)/while/body/add") is None


def test_stages_of_nested_loop_and_compiler_made():
    st = stages.stages_of(HLO)
    # innermost scope, through a transform
    assert st["fusion.5"] == "search.topk"
    # a compiler-made copy takes the stage of what it copies
    assert st["copy.6"] == "search.topk"
    # a loop-carried value: the stage of what the body produced for it
    assert st["gte.4"] == "search.topk"
    assert st["gte.19"] == st["copy.20"] == "search.topk"
    # the loop's own counter, the loop op and an argument's copy: no stage
    for name in ("add.7", "compare.12", "while.18", "copy.14"):
        assert name not in st, name
    assert st["sort.15"] == "search.topk"
    assert st["closed_call.21"] == st["copy.22"] == "search.node_state"
    # the kernel is still named by its own component
    assert tracing.kernels_of(HLO) == {"closed_call.21": "search_wave_bes"}


def synthetic():
    """Two executions of the program in two steps; a loop op (while.18)
    spans its body's ops, which carry stages; an op outside the program."""
    ops = [["sort.15", 10, 10], ["while.18", 20, 50], ["fusion.5", 25, 10],
           ["copy.6", 40, 5], ["add.7", 50, 2], ["closed_call.21", 72, 6],
           ["fusion.5", 130, 20], ["copy.99", 160, 5], ["fusion.5", 185, 5]]
    mods = [["jit_step(1)", 5, 80], ["jit_step(1)", 125, 30],
            ["jit_other(2)", 158, 10]]
    host = [["chipbench.step", 0, 100], ["chipbench.step", 120, 80]]
    return tracing.Trace(devices={"/device:TPU:0": {tracing.OPS: ops,
                                                    tracing.MODULES: mods}},
                         host=host)


def test_stage_self_times():
    tr = synthetic()
    st = stages.stages_of(HLO)
    per = stages.stage_self_ns(tr, st, "jit_step")
    # while.18: 50 less its body's 10 + 5 + 2 = 33, and no stage of its own
    assert per == {"search.topk": 10 + 10 + 5 + 20, None: 33 + 2,
                   "search.node_state": 6}
    # copy.99 ran in another program, fusion.5 at 185 outside any execution
    assert stages.stage_self_ns(tr, st, "jit_other") == {None: 5}


def test_gap_in_sync_is_named_by_sync():
    spans = [(0.0, 100.0, "chipbench.step"), (1.0, 99.0, "serving.step"),
             (10.0, 95.0, "serving.sync"), (95.0, 97.0, "serving.commit"),
             (97.0, 98.0, "serving.admit")]
    assert stages.label(spans, 60.0, 70.0) == "serving.sync"
    # across phases: the innermost span that holds all of it
    assert stages.label(spans, 94.0, 98.0) == "serving.step"
    assert stages.label(spans, 95.5, 97.5) == "serving.commit"
    assert stages.label(spans, 99.5, 100.0) == "chipbench.step"
    assert stages.label(spans, 150.0, 160.0) == tracing.NO_SPAN
    tr = synthetic()
    # the longest gap, [78, 130], lies mostly in a sync inside the step
    gaps = stages.idle_gaps(tr, [["serving.sync", 85, 40, {}]], n=2)
    assert gaps[0][0] == "serving.sync" and gaps[0][1] == pytest.approx(
        52e-9)


@pytest.fixture(scope="module")
def recorded():
    """40 ms of a traced step boundary of ``qwen2-0.5b.code`` on one TPU
    v5 lite (the end of one step, its commit and an admission, the start
    of the next), recorded by ``stage_profile.py --fixture`` with the
    program's HLO, and the readings on it when it was recorded."""
    with gzip.open(FIXTURE / "qwen2-0.5b.code.40ms.json.gz", "rt") as f:
        rec = json.load(f)
    with gzip.open(FIXTURE / "qwen2-0.5b.code.hlo.txt.gz", "rt") as f:
        rec["hlo"] = f.read()
    return rec


def test_recorded_stage_readings(recorded):
    rec = recorded
    tr = tracing.Trace(rec["devices"], rec["host"], rec["kernels"])
    got = stage_profile.readings(tr, rec["spans"], rec["stages"],
                                 rec["module"], len(tr.steps))
    assert got == rec["expect"]
    # the seven: six stages and the admissions
    assert set(got["stages_ms"]) == set(stages.STAGES)
    assert got["admit_ms"] > 0
    assert got["coverage"] >= 0.95
    assert got["six_stages_ms"] + got["remainder_ms"] == pytest.approx(
        got["search_program_ms"])


def test_recorded_program_maps_to_stages(recorded):
    """The recorded map is what ``stages_of`` reads from the program, and
    the ops that lead the breakdown land where the program put them."""
    st = stages.stages_of(recorded["hlo"])
    assert {n: st[n] for n in recorded["stages"]} == recorded["stages"]
    assert set(st.values()) == set(stages.STAGES)
    want = {"sort.18": "search.topk", "sort.19": "search.topk",
            "copy.285": "search.node_state", "copy.288": "search.node_state",
            "flash_attention.16": "search.root",
            "flash_attention.17": "search.root",
            "closed_call.52": "search.playout",
            "closed_call.53": "search.expand",
            "search_wave_bes.8": "search.tree"}
    assert {n: st.get(n) for n in want} == want
    # the scopes leave the kernels' names as the kernel metrics read them
    assert sorted(set(tracing.kernels_of(recorded["hlo"]).values())) == [
        "decode_attention", "flash_attention", "search_wave_bes"]


def test_recorded_spans(recorded):
    spans = recorded["spans"]
    steps = [(s, s + d) for n, s, d, _ in spans if n == "serving.step"]
    for n, s, d, meta in spans:
        if n != "serving.step":
            assert any(a <= s and s + d <= b for a, b in steps), n
        if n == "serving.admit":
            assert {"uid", "slot"} <= set(meta)
    tr = tracing.Trace(recorded["devices"], recorded["host"],
                       recorded["kernels"])
    assert stage_profile.inside_steps(tr, spans, recorded["module"])
    labels = {name for name, _ in stages.idle_gaps(tr, spans)}
    assert labels & {"serving.sync", "serving.search", "serving.commit",
                     "serving.admit"}


def test_profile_runs_on_the_cpu(tmp_path):
    """The whole script at the tiny size: the CPU has no device plane, so
    only the host's part reads anything."""
    import tiny
    res = stage_profile.profile(tiny.workload(), 5, 2, 2,
                                cache_dir=tmp_path / "cache",
                                require_chip=False)
    assert res["spans"] == ["serving.admit", "serving.commit",
                            "serving.search", "serving.step", "serving.sync"]
    assert res["admit_has_uid"] and res["inside_serving_step"]
    assert res["stages_in_program"] == sorted(stages.STAGES)
    assert len(res["traced_step_s"]) == 2 and res["admit_ms"] > 0
