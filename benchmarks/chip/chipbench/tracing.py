"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into plain lists: for each
device plane its op and module events, and the benchmark's own host spans,
all as ``[name, start_ns, duration_ns]`` on the trace's one clock.  An op is
named by its HLO instruction (``fusion.12``; the trace's own names carry the
whole instruction text).  ``Trace`` then answers the questions the readers
ask: device busy time as the union of op intervals, time in the Pallas
kernels (found by instruction through the compiled program's HLO,
``kernels_of``), time inside one program's executions, and idle gaps
labelled with the host span they fell in.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS, MODULES = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "chipbench."
STEP_SPAN = "chipbench.step"
CLIENT_SPAN = "chipbench.clients"
NO_SPAN = "no span"
# op_name path components that wrap a kernel call without naming it
_WRAPPERS = {"pallas_call", "closed_call", "while", "body", "cond", "scan",
             "checkpoint", "remat", "vmap()"}

Interval = Tuple[float, float]


def short_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..), ..`` -> ``fusion.12``."""
    return name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def kernels_of(hlo: str) -> Dict[str, str]:
    """{instruction: kernel} for every Pallas call of a compiled module's
    HLO text, the kernel named by the innermost component of the call's
    ``op_name`` that is no wrapper (``decode_attention``,
    ``search_wave_bes``, ...)."""
    out = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        parts = [p for p in (m.group(1) if m else "").split("/")
                 if p and p not in _WRAPPERS and not p.startswith("jit(")]
        out[short_name(line.strip())] = parts[-1] if parts else "pallas_call"
    return out


def load(logdir: str) -> Dict:
    """Read the one ``*.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, found "
                           f"{paths}")
    devices: Dict[str, Dict[str, List]] = {}
    host: List = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    lines[line.name] = [
                        [short_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Both merged and sorted."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _intervals(events) -> List[Interval]:
    return [(float(s), float(s) + float(d)) for _, s, d in events]


def self_times(events) -> Dict[str, float]:
    """Each op name's time with the ops nested inside it taken out (a loop
    op spans its body's ops on the same line)."""
    per: Dict[str, float] = {}
    stack: List[List] = []                       # [end, name]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        s, e = float(s), float(s) + float(d)
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            per[parent] = per.get(parent, 0.0) - (e - s)
        per[name] = per.get(name, 0.0) + (e - s)
        stack.append([e, name])
    return per


@dataclasses.dataclass
class Trace:
    """A loaded trace, cut to the window spanned by the benchmark's step
    spans.  ``kernels`` maps the per-token program's Pallas calls to kernel
    names (``kernels_of``).  Every time is in nanoseconds on the trace's
    clock."""
    devices: Dict[str, Dict[str, List]]
    host: List
    kernels: Dict[str, str] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        steps = sorted((float(s), float(s) + float(d))
                       for n, s, d in self.host if n == STEP_SPAN)
        self.steps: List[Interval] = steps
        self.lo = steps[0][0] if steps else 0.0
        self.hi = steps[-1][1] if steps else 0.0
        self._ops = {dev: [e for e in lines.get(OPS, [])
                           if self.lo <= float(e[1]) < self.hi]
                     for dev, lines in self.devices.items()}
        self._busy = {dev: clip(union(_intervals(ops)), self.lo, self.hi)
                      for dev, ops in self._ops.items()}

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def _modules(self, dev: str, module: str) -> List[Interval]:
        return clip(union(_intervals(
            e for e in self.devices[dev].get(MODULES, [])
            if e[0].startswith(module))), self.lo, self.hi)

    def check_complete(self, module: str, steps: int) -> None:
        """Every traced step holds an execution of the per-token program
        on every chip; a trace whose buffer overflowed does not."""
        if len(self.steps) != steps:
            raise RuntimeError(f"trace holds {len(self.steps)} of {steps} "
                               f"step spans")
        for dev in self.devices:
            mods = self._modules(dev, module)
            for s, e in self.steps:
                if not intersect([(s, e)], mods):
                    raise RuntimeError(
                        f"{dev}: no execution of {module} in the step span "
                        f"[{s}, {e}]: the trace is incomplete")

    def busy_ns(self) -> float:
        """Union of op intervals in the window, averaged over the chips."""
        if not self._busy:
            return 0.0
        return sum(map(length, self._busy.values())) / len(self._busy)

    def idle_in_steps_ns(self) -> float:
        """Device idle time inside the step spans, averaged over chips."""
        total = 0.0
        for busy in self._busy.values():
            total += length(self.steps) - length(intersect(self.steps, busy))
        return total / max(len(self._busy), 1)

    def kernel_ns(self, prefixes: Sequence[str]) -> Optional[float]:
        """Summed durations of the Pallas kernels whose name starts with one
        of ``prefixes``, averaged over chips; None if none ran."""
        insts = {i for i, k in self.kernels.items()
                 if k.startswith(tuple(prefixes))}
        total, found = 0.0, False
        for ops in self._ops.values():
            for name, s, d in ops:
                if name in insts:
                    total += float(d)
                    found = True
        return total / len(self.devices) if found else None

    def module_busy_ns(self, module: str) -> Optional[float]:
        """Union of op time inside the executions of the program whose
        module name starts with ``module``, averaged over chips."""
        total, found = 0.0, False
        for dev in self.devices:
            mods = self._modules(dev, module)
            if mods:
                found = True
                total += length(intersect(self._busy[dev], mods))
        return total / len(self.devices) if found else None

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of any chip in the window, in
        seconds, each named by the host span that covers most of it."""
        gaps = []
        for busy in self._busy.values():
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            gaps.extend((e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                        if e > s)
        spans = sorted((float(s), float(s) + float(d), name)
                       for name, s, d in self.host if name != STEP_SPAN)
        spans += [(s, e, STEP_SPAN) for s, e in self.steps]
        return [(_label(spans, s, e), ns * 1e-9)
                for ns, s, e in heapq.nlargest(n, gaps)]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` ops with the most self time in the window, in seconds
        averaged over chips; a Pallas call is named by its kernel."""
        per: Dict[str, float] = {}
        for ops in self._ops.values():
            for name, ns in self_times(ops).items():
                key = self.kernels.get(name, name)
                per[key] = per.get(key, 0.0) + ns
        k = max(len(self.devices), 1)
        top = heapq.nlargest(n, per.items(), key=lambda kv: kv[1])
        return [(name, ns * 1e-9 / k) for name, ns in top]

    def breakdown(self, n: int = 10) -> Dict[str, List]:
        return {"device_ops": [list(x) for x in self.top_ops(n)],
                "idle_gaps": [list(x) for x in self.idle_gaps(n)]}


def _label(spans, s: float, e: float) -> str:
    """The host span with the largest overlap with [s, e]; a span other
    than the step wins a tie, being the more specific."""
    best, best_ov = NO_SPAN, 0.0
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
