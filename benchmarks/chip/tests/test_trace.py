"""The reduction from a profiler trace to the per-layer numbers."""
import gzip
import json
import pathlib
import types

import pytest

from chipbench import spec, tracing

FIXTURE = pathlib.Path(__file__).parent / "fixtures"


def synthetic():
    # two steps of 100 ns on one chip; ops (some overlapping, some in
    # kernels) inside the program's two executions
    ops = [["fusion.1", 10, 30], ["search_wave_se.4", 20, 20],
           ["closed_call.3", 60, 10], ["copy.2", 70, 15],
           ["fusion.1", 130, 40], ["flash_attention.7", 180, 10]]
    mods = [["jit_step(7)", 5, 85], ["jit_step(7)", 125, 70]]
    host = [["chipbench.step", 0, 100], ["chipbench.clients", 100, 20],
            ["chipbench.step", 120, 80], ["python other", 0, 5]]
    kernels = {"search_wave_se.4": "search_wave_se",
               "closed_call.3": "decode_attention",
               "flash_attention.7": "flash_attention"}
    return tracing.Trace(devices={"/device:TPU:0": {tracing.OPS: ops,
                                                    tracing.MODULES: mods}},
                         host=[h for h in host
                               if h[0].startswith(tracing.SPAN_PREFIX)],
                         kernels=kernels)


def test_names_and_kernels():
    hlo = ('  %closed_call.53 = bf16[9,1,64]{2,1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call", metadata={op_name="jit('
           'step)/vmap()/while/body/decode_attention/while/body/'
           'closed_call/pallas_call" stack_frame_id=3}\n'
           '  %search_wave_bes.8 = (s32[4]) custom-call(%b), '
           'custom_call_target="tpu_custom_call", metadata={op_name="jit('
           'step)/while/body/closed_call/search_wave_bes/pallas_call"}\n'
           '  %fusion.2 = f32[4] fusion(%c), kind=kLoop\n')
    assert tracing.kernels_of(hlo) == {"closed_call.53": "decode_attention",
                                       "search_wave_bes.8": "search_wave_bes"}
    assert tracing.short_name("%while.335 = (s32[], f32[4]) while(%t)") == (
        "while.335")
    assert tracing.short_name("fusion.12") == "fusion.12"


def test_union_and_intersect():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tracing.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]


def test_synthetic_reduction():
    tr = synthetic()
    assert (tr.lo, tr.hi, tr.window_ns) == (0, 200, 200)
    # busy: [10, 40] + [60, 85] + [130, 170] + [180, 190]
    assert tr.busy_ns() == 30 + 25 + 40 + 10
    # idle in steps: 100 - 55 in the first, 80 - 50 in the second
    assert tr.idle_in_steps_ns() == 45 + 30
    assert tr.kernel_ns(("search_wave", "uct_select")) == 20
    assert tr.kernel_ns(("decode_attention",)) == 10
    assert tr.kernel_ns(("nothing",)) is None
    assert tr.module_busy_ns("jit_step") == 30 + 25 + 40 + 10
    assert tr.module_busy_ns("jit_other") is None
    gaps = tr.idle_gaps(3)
    # [85, 130] overlaps the clients' span most
    assert [g[0] for g in gaps] == ["chipbench.clients", "chipbench.step",
                                    "chipbench.step"]
    assert [g[1] for g in gaps] == pytest.approx([45e-9, 20e-9, 10e-9])
    # self time: fusion.1 holds the search_wave call for 20 of its 30 ns
    assert dict(tr.top_ops()) == pytest.approx({
        "fusion.1": 50e-9, "search_wave_se": 20e-9, "copy.2": 15e-9,
        "decode_attention": 10e-9, "flash_attention": 10e-9})
    tr.check_complete("jit_step", 2)
    with pytest.raises(RuntimeError):
        tr.check_complete("jit_other", 2)


def _ctx(tr):
    return types.SimpleNamespace(trace=tr, program={"module": "jit_step"})


def test_readers_on_synthetic():
    tr = synthetic()
    read = lambda m: spec.reader(m)(_ctx(tr))
    assert read("device_idle_share") == pytest.approx(100 * (1 - 105 / 200))
    assert read("step_idle_ms") == pytest.approx(75 / 2 * 1e-6)
    assert read("search_wave_ms") == pytest.approx(10e-6)
    assert read("search_program_ms") == pytest.approx(105 / 2 * 1e-6)
    assert spec.reader("step_idle_ms")(_ctx(None)) is None


def test_recorded_trace():
    """The first 40 ms of a traced step of ``smollm-135m.chat`` on one TPU
    v5 lite (op names cut to their HLO instruction, the program's kernel
    map beside them), and the reduction's values on it when it was
    recorded."""
    with gzip.open(FIXTURE / "smollm-135m.chat.40ms.json.gz", "rt") as f:
        rec = json.load(f)
    tr = tracing.Trace(rec["devices"], rec["host"], rec["kernels"])
    want = rec["expect"]
    assert tr.chips == 1 and len(tr.steps) == 1
    assert tr.window_ns == want["window_ns"] == 40e6
    assert tr.busy_ns() == want["busy_ns"]
    assert tr.idle_in_steps_ns() == want["idle_in_steps_ns"]
    assert tr.busy_ns() + tr.idle_in_steps_ns() == tr.window_ns
    for key, prefixes in (("search_wave_ns", ("search_wave", "uct_select")),
                          ("decode_attention_ns", ("decode_attention",)),
                          ("flash_attention_ns", ("flash_attention",))):
        assert tr.kernel_ns(prefixes) == want[key] > 0
    assert tr.breakdown() == json.loads(json.dumps(want["breakdown"]))
    # the step starts with the root prefill: both flash_attention calls
    assert tr.top_ops(1)[0][0] == "flash_attention"
