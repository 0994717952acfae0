#!/usr/bin/env python3
"""Split a cell's per-token program time by search stage, from one trace.

    python3 benchmarks/chip/stage_profile.py --workload qwen2-0.5b.code \\
        --seed 7 --steps 3 --untraced 8 [--fixture F.json.gz --hlo H.txt.gz]

Serves the cell's traffic through the engine the benchmark builds (same
weights, slots and search), times ``--untraced`` steps with no profiler,
then traces ``--steps`` steps and reads from that one trace, per traced
step: the program's device time (``search_program_ms``'s reduction), the
self time of each of the six stage scopes (``chipbench/stages.py``) and of
the ops no stage names, the ``serving.admit`` spans' summed time, the idle
gaps labelled by the innermost host span, and whether every execution of
the program lies inside a ``serving.step`` span.  The median step with the
profiler on against the median with it off is the cost of tracing.

``--fixture`` also writes a few ms of the trace around a step boundary
with an admission (ops, module executions, host spans, the kernel and
stage maps of the instructions in it, and the readings on it), gzipped,
and ``--hlo`` the compiled program's HLO text, gzipped, both for
``tests/test_stages.py``.  Prints one JSON object as its last line of
standard output; needs a chip.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import cell, spec, stages, tracing  # noqa: E402
from chipbench.traffic import RequestStream  # noqa: E402

STEP, ADMIT = "serving.step", "serving.admit"


def compile_program(jax, eng):
    """The engine's own per-token program, compiled: (module, HLO text)."""
    import jax.numpy as jnp
    args = (jnp.asarray(eng.prefix_buf), jnp.asarray(eng.prefix_len),
            jax.random.key(0))
    if eng._carry is not None:
        args += (eng._carry,)
    hlo = eng._mcts_search.lower(*args).compile().as_text()
    head = hlo.split("\n", 1)[0].split()
    return head[1].rstrip(",") if head[0] == "HloModule" else "", hlo


def readings(tr, spans, stage_map, module, steps):
    """The per-step numbers of one trace (ms unless named otherwise)."""
    per = stages.stage_self_ns(tr, stage_map, module)
    ms = lambda ns: ns / steps * 1e-6
    prog = tr.module_busy_ns(module) or 0.0
    out = {"search_program_ms": ms(prog),
           "stages_ms": {s: ms(per.get(s, 0.0)) for s in stages.STAGES},
           "unscoped_ms": ms(per.get(None, 0.0)),
           "admit_ms": ms(stages.span_ns(spans, ADMIT, tr.lo, tr.hi))}
    six = sum(out["stages_ms"].values())
    out["six_stages_ms"] = six
    out["remainder_ms"] = out["search_program_ms"] - six
    out["coverage"] = six / out["search_program_ms"] if prog else None
    return out


def inside_steps(tr, spans, module) -> bool:
    """Every execution of the program lies inside a ``serving.step``
    span, on the trace's one clock."""
    steps = [(float(s), float(s) + float(d))
             for n, s, d, *_ in spans if n == STEP]
    for lines in tr.devices.values():
        for name, s, d in lines.get(tracing.MODULES, []):
            s, e = float(s), float(s) + float(d)
            if name.startswith(module) and tr.lo <= s < tr.hi and not any(
                    a <= s and e <= b for a, b in steps):
                return False
    return True


def top_unscoped(tr, stage_map, module, hlo, n=8):
    """The program's ops that map to no stage, most self time first, with
    their opcode and ``op_name``."""
    info = {i.name: [i.opcode, i.op_name] for i in stages._parse(hlo)}
    per = {}
    for dev in tr.devices:
        for name, ns in tracing.self_times(
                stages._program_ops(tr, dev, module)).items():
            if name not in stage_map:
                per[name] = per.get(name, 0.0) + ns
    steps = max(len(tr.steps), 1) * max(tr.chips, 1)
    return sorted(([k, v / steps * 1e-6] + info.get(k, [])
                   for k, v in per.items()), key=lambda kv: -kv[1])[:n]


def fixture(tr, spans, stage_map, module, before_ms=20.0, after_ms=20.0):
    """A window of the trace around the first step boundary with an
    admission near it, cut to what ``Trace`` and ``stages`` read."""
    ends = [e for _, e in tr.steps[:-1]]
    admits = [float(s) for n, s, *_ in spans if n == ADMIT]
    end = next((e for e in ends if any(e - before_ms * 1e6 <= a <= e
                                       + after_ms * 1e6 for a in admits)),
               ends[0] if ends else tr.steps[0][1])
    lo, hi = end - before_ms * 1e6, end + after_ms * 1e6

    def cut(events):
        return [[n, float(s), float(d)] for n, s, d, *_ in events
                if float(s) < hi and float(s) + float(d) > lo]

    # ops wholly inside the window, so that a loop op keeps its body's
    # ops and self times add up; module executions clipped by ``Trace``
    devices = {dev: {k: [e for e in cut(v) if k != tracing.OPS or (
        lo <= e[1] and e[1] + e[2] <= hi)] for k, v in lines.items()}
               for dev, lines in tr.devices.items()}
    host = [[n, max(float(s), lo), min(float(s) + float(d), hi)
             - max(float(s), lo)] for n, s, d in cut(tr.host)]
    spans_cut = [[n, float(s), float(d), dict(m)] for n, s, d, m in spans
                 if float(s) < hi and float(s) + float(d) > lo]
    names = {e[0] for lines in devices.values()
             for e in lines.get(tracing.OPS, [])}
    rec = {"devices": devices, "host": host, "spans": spans_cut,
           "module": module,
           "kernels": {k: v for k, v in tr.kernels.items() if k in names},
           "stages": {k: v for k, v in stage_map.items() if k in names}}
    small = tracing.Trace(devices, host, rec["kernels"])
    rec["expect"] = readings(small, spans_cut, rec["stages"], module,
                             len(small.steps))
    return rec


def profile(wl, seed: int, steps: int, untraced: int, fixture_path: str = "",
            hlo_path: str = "", cache_dir: pathlib.Path = ROOT / ".jax_cache",
            require_chip: bool = True):
    """The readings of one run (module docstring)."""
    import contextlib

    import jax
    cell.use_compile_cache(jax, cache_dir)
    devices = (cell.chip_devices(jax, wl.chips) if require_chip
               else jax.devices()[:wl.chips])
    t = wl.traffic
    eng, _ = cell.build_engine(jax, wl, seed, devices)
    module, hlo = compile_program(jax, eng)
    stage_map = stages.stages_of(hlo)
    kernels = tracing.kernels_of(hlo)
    clients = cell.Clients(
        eng, RequestStream(t, seed, wl.config["vocab_size"]),
        t["clients_per_slot"] * t["slots_per_chip"] * len(devices))
    for _ in range(t["warmup_steps"]):
        eng.step()
        clients.after_step(time.perf_counter())

    def run(n, span):
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            with span(tracing.STEP_SPAN):
                eng.step()
            out.append(time.perf_counter() - t0)
            with span(tracing.CLIENT_SPAN):
                clients.after_step(time.perf_counter())
        return out

    off = run(untraced, lambda name: contextlib.nullcontext())
    logdir = tempfile.mkdtemp(prefix="stage-profile-")
    jax.profiler.start_trace(logdir)
    on = run(steps, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    tr = tracing.Trace(**tracing.load(logdir), kernels=kernels)
    spans = stages.load_spans(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    tr.check_complete(module, steps)

    res = readings(tr, spans, stage_map, module, steps)
    res.update({
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "steps": steps,
        "untraced_step_s": off, "traced_step_s": on,
        "untraced_median_s": statistics.median(off) if off else None,
        "traced_median_s": statistics.median(on),
        "inside_serving_step": inside_steps(tr, spans, module),
        "spans": sorted({n for n, *_ in spans}),
        "admit_has_uid": all("uid" in m and "slot" in m
                             for n, _, _, m in spans if n == ADMIT),
        "idle_gaps": stages.idle_gaps(tr, spans),
        "top_unscoped": top_unscoped(tr, stage_map, module, hlo),
        "kernels": sorted(set(kernels.values())),
        "stages_in_program": sorted(set(stage_map.values())),
    })
    print(f"stage_profile: unscoped remainder {res['remainder_ms']} ms of "
          f"{res['search_program_ms']} ms per step", file=sys.stderr)
    if hlo_path:
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo)
    if fixture_path:
        rec = fixture(tr, spans, stage_map, module)
        with gzip.open(fixture_path, "wt") as f:
            json.dump(rec, f)
        res["fixture_expect"] = rec["expect"]
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--untraced", type=int, default=8)
    ap.add_argument("--fixture", default="")
    ap.add_argument("--hlo", default="")
    args = ap.parse_args(argv)
    res = profile(spec.workload(args.workload), args.seed, args.steps,
                  args.untraced, args.fixture, args.hlo)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
