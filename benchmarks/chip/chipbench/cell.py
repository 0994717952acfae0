"""One run of one cell: set-up, the measured window, the metric readers and
the comparison that decides ``correct``.

The path under test is the one users run, ``ServingEngine(decode="mcts")``:
each engine step is one batched multi-root search per emitted token.  The
traffic is a closed loop: ``clients_per_slot`` clients per slot each keep one
request outstanding and send the next as soon as it finishes, so a backlog
keeps every slot busy and requests are admitted all through the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
import types
from typing import Dict, List

from chipbench import check, tracing, work
from chipbench.spec import Workload, peaks
from chipbench.traffic import RequestStream


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def chip_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"chipbench: JAX found no accelerator, only "
                     f"{len(devs)} cpu device(s)")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def use_compile_cache(jax, cache_dir: pathlib.Path) -> None:
    """Every program in the persistent cache at a fixed path, so that only
    the first run of a cell in a checkout compiles."""
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Flight:
    req: object
    times: List[float] = dataclasses.field(default_factory=list)


class Clients:
    """The closed loop: one outstanding request per client."""

    def __init__(self, eng, stream: RequestStream, n: int):
        from repro.serving.scheduler import Request
        self._request = Request
        self.eng, self.stream = eng, stream
        self.flights: Dict[int, Flight] = {}
        self.done: List[Flight] = []
        self._uid = 0
        for _ in range(n):
            self.send()

    def send(self) -> None:
        prompt, olen = self.stream.next()
        req = self._request(uid=self._uid, prompt=prompt,
                            max_new_tokens=olen)
        self._uid += 1
        self.flights[req.uid] = Flight(req)
        self.eng.submit(req)

    def after_step(self, t: float) -> None:
        """Stamp the tokens the step committed; each finished request's
        client sends its next one at once."""
        for uid in list(self.flights):
            f = self.flights[uid]
            f.times.extend([t] * (len(f.req.out_tokens) - len(f.times)))
            if f.req.done:
                self.done.append(self.flights.pop(uid))
                self.send()

    def all(self) -> List[Flight]:
        return self.done + list(self.flights.values())


def build_engine(jax, wl: Workload, seed: int, devices):
    from repro.models.base import ModelConfig
    from repro.parallel.compat import mesh_from_devices
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.mcts_decode import MCTSDecodeConfig

    t = wl.traffic
    cfg = ModelConfig(**wl.reference.program_config(wl.config))
    weights = wl.reference.make_weights(wl.config, seed,
                                        wl.config["serve_dtype"])
    # the program's own default (None) shards the slots over every visible
    # device; a cell that asks for fewer chips than the host has pins them
    if len(jax.devices()) == len(devices):
        mesh = None
    elif len(devices) == 1:
        mesh = False
    else:
        mesh = mesh_from_devices(devices)
    eng = ServingEngine(cfg, weights, EngineConfig(
        max_batch=t["slots_per_chip"] * len(devices), max_seq=t["max_seq"],
        decode="mcts", mcts=MCTSDecodeConfig(**t["search"]), mesh=mesh))
    return eng, weights


def step_program(jax, eng) -> Dict:
    """Compile the engine's own per-token program (from the persistent
    cache after a cell's first run): its module name, as the trace names
    its executions, its Pallas kernels by instruction name, and the bytes
    of its compile-time memory analysis."""
    import jax.numpy as jnp
    args = (jnp.asarray(eng.prefix_buf), jnp.asarray(eng.prefix_len),
            jax.random.key(0))
    if eng._carry is not None:
        args += (eng._carry,)
    compiled = eng._mcts_search.lower(*args).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    head = hlo.split("\n", 1)[0].split()
    return {"module": head[1].rstrip(",") if head[0] == "HloModule" else "",
            "kernels": tracing.kernels_of(hlo),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes)}


def run(wl: Workload, seed: int, seconds: float, trace: bool, t_proc: float,
        cache_dir: pathlib.Path, require_chip: bool = True,
        control=None) -> Dict:
    """One run.  ``control`` (a precision of the reference, see ``check``)
    puts the lower-precision control in the program's place for the
    verdict, and keeps the program's own under ``sound``: for
    ``control.py``, never in the benchmark's own runs."""
    import jax
    use_compile_cache(jax, cache_dir)
    devices = (chip_devices(jax, wl.chips) if require_chip
               else jax.devices()[:wl.chips])
    kind = devices[0].device_kind
    peak = peaks(kind) if require_chip else {"bf16_flops_per_s": 1.0}
    t = wl.traffic

    eng, weights = build_engine(jax, wl, seed, devices)
    program = step_program(jax, eng)
    log(f"chipbench: per-token program "
        f"{ {k: v for k, v in program.items() if k != 'kernels'} }, "
        f"kernels {sorted(set(program['kernels'].values()))}")
    clients = Clients(eng, RequestStream(t, seed, wl.config["vocab_size"]),
                      t["clients_per_slot"] * t["slots_per_chip"]
                      * len(devices))
    for _ in range(t["warmup_steps"]):
        eng.step()
        clients.after_step(time.perf_counter())

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event.endswith("backend_compile_duration") else None)
    # a traced run traces the window's first ``trace_steps`` steps only: the
    # device's trace buffer holds a few steps of this program
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    no_span = lambda name: contextlib.nullcontext()
    span = jax.profiler.TraceAnnotation if trace else no_span
    t_start = time.perf_counter()
    setup_s = t_start - t_proc
    step_s = []
    traced_end, stalled = None, 0.0
    now = t_start
    while True:
        with span(tracing.STEP_SPAN):
            eng.step()
        step_s.append(time.perf_counter() - now)
        now = time.perf_counter()
        with span(tracing.CLIENT_SPAN):
            clients.after_step(now)
        if trace and traced_end is None and len(step_s) == t["trace_steps"]:
            jax.profiler.stop_trace()
            traced_end, span = now, no_span
            # the window serves ``seconds`` besides the time spent stopping
            # the profiler, so that a traced run finishes requests too
            stalled = time.perf_counter() - now
            now += stalled
        if now - t_start - stalled >= seconds and (traced_end or not trace):
            break
    t_end = now
    slowest = max(range(len(step_s)), key=step_s.__getitem__)
    log(f"chipbench: window {t_end - t_start} s, {len(step_s)} steps "
        f"(median {statistics.median(step_s)} s, slowest {step_s[slowest]} "
        f"s at step {slowest}), {len(compiles)} compilations in it")

    stats = [d.memory_stats() or {} for d in devices]
    stat_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    prog_bytes = (program["argument_bytes"] + program["temp_bytes"]
                  + program["output_bytes"] - program["alias_bytes"])
    log(f"chipbench: peak_bytes_in_use {stat_peak}, per-token program "
        f"{prog_bytes} bytes per chip")

    ctx = window_context(wl, clients.all(), t_start, t_end, len(step_s),
                         setup_s, program, peak, len(devices))
    attempted = ctx.attempted
    if trace:
        # the per-layer metrics read the traced steps alone
        ctx = window_context(wl, clients.all(), t_start, traced_end,
                             t["trace_steps"], setup_s, program, peak,
                             len(devices))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(stat_peak, prog_bytes)}
    extra: Dict = {}
    if trace:
        tr = tracing.Trace(**tracing.load(logdir),
                           kernels=program["kernels"])
        shutil.rmtree(logdir, ignore_errors=True)
        tr.check_complete(program["module"], t["trace_steps"])
        ctx.trace = tr
        device["busy_s"] = tr.busy_ns() * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        extra["breakdown"] = tr.breakdown()
        log(f"chipbench: traced {len(tr.steps)} steps on {tr.chips} "
            f"chip(s), window {device['window_s']} s, busy "
            f"{device['busy_s']} s")
    metrics = {}
    for m in (wl.per_layer if trace else wl.end_to_end):
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    finished = [(f.req.prompt, list(f.req.out_tokens), f.req.max_new_tokens)
                for f in clients.done]
    # what the requests still in flight were served counts as well
    served = finished + [(f.req.prompt, list(f.req.out_tokens), None)
                         for f in clients.flights.values()
                         if f.req.out_tokens]
    del eng, weights, clients
    gc.collect()
    verdict = judge(wl, seed, served, control)
    if control is not None:
        extra["sound"] = judge(wl, seed, served)
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": verdict["failed"], "metrics": metrics,
              "device": device, **extra, "compared": verdict["compared"]}
    for name, c in verdict["compared"].items():
        log(f"compared {name} {c['value']} limit {c['limit']}")
    return result


def window_context(wl: Workload, flights: List[Flight], t_start: float,
                   t_end: float, steps: int, setup_s: float, program: Dict,
                   peak: Dict, chips: int) -> types.SimpleNamespace:
    """What the metric readers read of the window: its length and set-up,
    the tokens committed in it, the gaps between consecutive tokens of one
    request that end in it, and the operations its work needs."""
    ref, conf, search = wl.reference, wl.config, wl.traffic["search"]
    tokens, gaps, flops, attempted = 0, [], 0, 0
    for f in flights:
        plen = len(f.req.prompt)
        inside = [i for i, ts in enumerate(f.times) if t_start < ts <= t_end]
        if not inside:
            continue
        attempted += 1
        tokens += len(inside)
        gaps.extend(f.times[i] - f.times[i - 1] for i in inside if i > 0)
        flops += sum(work.committed_token_flops(ref, conf, search, plen + i)
                     for i in inside)
        if inside[0] == 0:
            flops += work.prefill_flops(ref, conf, plen)
    return types.SimpleNamespace(
        window_s=t_end - t_start, setup_s=setup_s, steps=steps,
        tokens=tokens, gaps_s=gaps, flops=flops, attempted=attempted,
        program=program, peak=peak, chips=chips, trace=None)


def judge(wl: Workload, seed: int, served, control=None) -> Dict:
    """Every finished request has all its tokens, each served token is in
    the vocabulary, and a sample of the requests, the longest among them,
    agrees with the reference; with ``control``, the control's tokens are
    judged in place of the served ones."""
    t, V = wl.traffic, wl.config["vocab_size"]
    bad = [s for s in served
           if (s[2] is not None and len(s[1]) != s[2])
           or not all(0 <= x < V for x in s[1])]
    picked = check.sample([(p, o) for p, o, _ in served], seed,
                          t["check_requests"])
    res = check.compare(wl.reference, wl.config, seed, picked,
                        t["search"]["num_actions"], t["max_seq"], control)
    limit = wl.limits["top_a_gap"]["limit"]
    over = sum(g > limit for g in res["gaps"])
    log(f"chipbench: {control or 'served'} tokens: checked {len(picked)} "
        f"requests, {res['tokens_checked']} tokens, by reference rank "
        f"{res['ranks']} ({res['outside_top_a']} outside the top-A); "
        f"{len(bad)} of {len(served)} requests short or out of vocabulary")
    ok = bool(picked) and not bad and over == 0
    return {"correct": ok, "failed": len(bad) + over, "ranks": res["ranks"],
            "compared": {"top_a_gap": {"value": res["top_a_gap"],
                                       "limit": limit}}}
