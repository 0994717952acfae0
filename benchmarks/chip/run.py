#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload qwen2-0.5b.code --seed 7 \\
        --seconds 30 --trace 0

Prints as its last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number that
decides ``correct`` beside its limit, which also end standard error.
Exits non-zero, printing no result, where JAX finds no accelerator or fewer
chips than the cell asks for.  Keeps JAX's compile cache in ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """``time.perf_counter()`` at the moment this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROC = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from chipbench import cell, spec
    wl = spec.workload(args.workload)
    result = cell.run(wl, args.seed, args.seconds, bool(args.trace), T_PROC,
                      cache_dir=ROOT / ".jax_cache")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
