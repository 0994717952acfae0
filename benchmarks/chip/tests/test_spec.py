"""``BENCHMARK.json`` and the files its cells name: every workload loads by
name, and the file keeps to the benchmark's format."""
import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_loads_by_name(name):
    wl = spec.workload(name)
    assert wl.chips in (1, 4)
    assert wl.limits["top_a_gap"]["limit"] > 0
    names = {m.name for m in wl.end_to_end}
    assert {"setup_s", "tokens_per_s", "itl_p95_ms"} <= names
    assert wl.per_layer
    for m in wl.end_to_end + wl.per_layer:
        assert callable(m.read)
    wl.reference.program_config(wl.config)
    assert wl.traffic["search"]["num_actions"] >= 1


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.workload("no-such-cell")


def test_format():
    assert set(BENCH) == KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = BENCH["workloads"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert json.load(open(spec.ROOT / c["file"]))["name"] == c["name"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in
                       c["reduced"])
    for w in cells:
        assert w["config"] in configs and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (spec.HERE / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for x in [c["name"] for c in BENCH["configs"]] + [w["name"] for w in
                                                      cells]:
        assert NAME.match(x)
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_peaks_are_keyed_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")
