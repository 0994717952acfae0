"""Benchmark harness — one module per paper artifact (see DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV.  Usage:
  PYTHONPATH=src python -m benchmarks.run [--only <module>] [--smoke]
                                          [--json PATH]

``--smoke`` runs every module at tiny budgets (CI perf-trajectory mode);
``--json PATH`` additionally writes the rows as a JSON list of
``{bench, name, us_per_call, derived}`` objects (the CI artifact
``BENCH_pr.json``).  Without ``--only``, ``shard_scaling`` runs only when
JAX sees several devices.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import sys
import traceback

import jax

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "pipeline_schedule",     # Figs 3/4/6 + steady-state throughput
    "playout_speedup",       # §II def. 1
    "strength_speedup",      # §II def. 2 + §IV baselines
    "search_overhead",       # §III-B
    "strength_bench",        # wu vs vloss at equal wall-clock (DESIGN §15)
    "mcts_decode_bench",     # modern instantiation (NN playouts)
    "serving_bench",         # request lifecycle: cold vs KV-splice+reuse
    "shard_scaling",         # batch axis over a device mesh (DESIGN.md §9)
    "ft_overhead",           # elastic driver at zero failures (DESIGN.md §13)
    "straggler_bench",       # runtime policy
    "kernel_bench",          # per-kernel micro numbers
    "ablations",             # vl-weight / in-flight / MoE-capacity knobs
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny budgets: exercise every module, fast")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON to PATH")
    args = ap.parse_args()
    enable_compile_cache()
    mods = [args.only] if args.only else MODULES
    if not args.only and jax.device_count() < 2:
        mods = [m for m in mods if m != "shard_scaling"]
        print("# shard_scaling left out: it needs several devices",
              file=sys.stderr)
    print("name,us_per_call,derived")

    rows = []
    current = [""]

    def report(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()
        rows.append({"bench": current[0], "name": name,
                     "us_per_call": round(float(us), 1), "derived": derived})

    failed = []
    for m in mods:
        current[0] = m
        try:
            mod = importlib.import_module(f"benchmarks.{m}")
            if "smoke" in inspect.signature(mod.run).parameters:
                mod.run(report, smoke=args.smoke)
            else:
                mod.run(report)
        except Exception as e:
            failed.append(m)
            print(f"{m},-1,ERROR {type(e).__name__}: {e}")
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
