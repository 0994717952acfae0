"""Loading a cell of ``BENCHMARK.json`` and the files it names."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = HERE.parents[1]                                  # the checkout


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclasses.dataclass
class Workload:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    reference: Any                      # module: references/<name>.py
    limits: Dict[str, Dict[str, Any]]   # number compared -> {"limit": ..}
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def reader(name: str) -> Callable[[Any], Optional[float]]:
    """``metrics/<name>.py``'s ``read``."""
    return load_module(HERE / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name.replace('.', '_')}").read


def metrics_for(entries, cell: str) -> List[Metric]:
    return [Metric(m["name"], m["unit"], reader(m["name"]))
            for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def workload(name: str, bench: Optional[Dict[str, Any]] = None,
             root: pathlib.Path = ROOT) -> Workload:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(root / conf_entry["file"])
    return Workload(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        reference=reference(config["reference"]),
        limits=_json(HERE / "limits" / f"{name}.json"),
        end_to_end=metrics_for(bench["end_to_end"], name),
        per_layer=metrics_for(bench["per_layer"], name))


def reference(name: str):
    return load_module(HERE / "references" / f"{name}.py",
                       f"chipbench_reference_{name}")


def peaks(kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of ``device_kind`` ``kind``; a kind
    missing from the table is an error, never a default."""
    table = _json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(it has {sorted(table)})")
    return table[kind]
