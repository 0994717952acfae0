#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's sound gap and the
precision control's, on many seeds, in one process.

    python3 benchmarks/chip/control.py --workload qwen2-0.5b.code \\
        --seeds 11,12,13 --seconds 25 --control int8

For each seed: a run of the cell at its own load (a short window, long
enough to finish the mix's longest requests), judged twice by the harness's
own ``judge``: the served tokens (``sound``), and the control put in the
program's place (the reference with its weights rounded to ``--control``,
committing at each served position the entry of its own top-A at an index
drawn from the seed).  Prints one JSON line per seed and a summary:
``lower`` (the largest sound gap) and ``upper`` (the control's smallest).
Exits non-zero where a sound run is not correct or a control run is.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--control", default="int8")
    args = ap.parse_args(argv)

    from chipbench import cell, spec
    wl = spec.workload(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = cell.run(wl, seed, args.seconds, False, time.perf_counter(),
                       cache_dir=ROOT / ".jax_cache", control=args.control)
        sound = res["sound"]
        row = {"seed": seed, "correct": sound["correct"],
               "top_a_gap": sound["compared"]["top_a_gap"]["value"],
               "ranks": sound["ranks"], "control_correct": res["correct"],
               "control_top_a_gap": res["compared"]["top_a_gap"]["value"],
               "tokens_per_s": res["metrics"]["tokens_per_s"]["value"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": wl.name, "control": args.control,
               "seeds": len(rows),
               "lower": max(r["top_a_gap"] for r in rows),
               "upper": min(r["control_top_a_gap"] for r in rows),
               "sound_correct": sum(r["correct"] for r in rows),
               "control_correct": sum(r["control_correct"] for r in rows)}
    print(json.dumps(summary), flush=True)
    ok = (summary["sound_correct"] == len(rows)
          and summary["control_correct"] == 0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
