"""The per-token program's device time split by search stage, and the
engine's own host spans.

The program names its stages with device scopes (``jax.named_scope``),
which the compiled HLO keeps in each instruction's ``op_name``: ``STAGES``
below.  ``stages_of`` maps each instruction of a compiled module to the
innermost stage in its ``op_name``.  An instruction the compiler made
itself (a copy, a select it sank into a loop, a dot it rewrote) carries no
``op_name``, or only an argument's name: it takes the stage of the value
it moves or computes from, followed through tuples, copies and loop
carries (a loop-carried value has the stage of what the loop's body
produced for it), else the stage of the instruction whose computation
holds it.  An instruction whose own ``op_name`` names no stage is the
search loop's plumbing (its counters, the loop op itself) and maps to
nothing.

``stage_self_ns`` sums the self time (``tracing.self_times``) of each
stage's ops inside the program's executions; ``load_spans`` reads the
engine's host spans (``serving.*``) from the same trace, on the same
clock, with their metadata (``uid``, ``slot``); ``label`` names an idle
gap by the shortest span among those covering the most of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import tracing

STAGES = ("search.root", "search.tree", "search.expand", "search.playout",
          "search.node_state", "search.topk")
SPAN_PREFIX = "serving."
_STAGE = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)"
                    % "|".join(re.escape(s) for s in STAGES))
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INST = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_CALLED_SET = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


def stage_of(op_name: str) -> Optional[str]:
    """The innermost stage scope in an ``op_name``; a transform around a
    scope (``vmap(search.topk)``) counts as the scope."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def _close(text: str, i: int) -> int:
    """Index just past the bracket group opening at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, "{": 1, "[": 1, ")": -1, "}": -1, "]": -1}.get(
            text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


class _Inst(NamedTuple):
    comp: str                   # the computation that holds it
    name: str
    opcode: str
    op_name: Optional[str]
    operands: List[str]
    called: List[str]           # computations it calls
    body: Optional[str]         # a while loop's body
    index: Optional[int]        # a tuple element's or a parameter's
    root: bool                  # its computation's ROOT


def _shape_end(text: str) -> int:
    """Index just past the shape an instruction's text starts with: a
    tuple ``(...)``, or ``dtype[dims]`` with an optional ``{layout}``."""
    if text.startswith("("):
        return _close(text, 0)
    i = text.find("[")
    i = _close(text, i) if i >= 0 else text.find(" ")
    return _close(text, i) if text[i:i + 1] == "{" else i


def _parse(hlo: str) -> List[_Inst]:
    out, comp = [], None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = _COMP.match(line)
            if m and line.rstrip().endswith("{"):
                comp = m.group(1)
            continue
        m = _INST.match(line)
        if not m:
            continue
        rest = line[m.end():]
        i = _shape_end(rest)
        j = rest.find("(", i)
        opcode, i = rest[i:j].strip(), j
        inner = rest[i:_close(rest, i)]
        attrs = rest[_close(rest, i):]
        called = _CALLED.findall(attrs) + [
            c.strip().lstrip("%") for grp in _CALLED_SET.findall(attrs)
            for c in grp.split(",") if c.strip()]
        op = re.search(r'op_name="([^"]*)"', attrs)
        body = re.search(r"\bbody=%?([\w.\-]+)", attrs)
        idx = re.search(r"\bindex=(\d+)", attrs)
        if opcode == "parameter":
            idx = re.match(r"\((\d+)\)", inner)
        out.append(_Inst(comp, m.group(1), opcode,
                         op.group(1) if op else None,
                         re.findall(r"%([\w.\-]+)", inner), called,
                         body.group(1) if body else None,
                         int(idx.group(1)) if idx else None,
                         line.lstrip().startswith("ROOT ")))
    return out


# ops that only move or regroup a value: their stage is the stage of what
# they move, unless their own op_name names one
_MOVES = {"get-tuple-element", "tuple", "parameter", "copy", "copy-start",
          "copy-done", "bitcast", "opt-barrier"}


def stages_of(hlo: str) -> Dict[str, str]:
    """{instruction: stage} over a compiled module's HLO text, for every
    instruction that maps to one (module docstring)."""
    insts = {i.name: i for i in _parse(hlo)}
    roots = {i.comp: i.name for i in insts.values() if i.root}
    caller: Dict[str, _Inst] = {}           # computation -> calling inst
    for i in insts.values():
        for c in i.called:
            caller.setdefault(c, i)

    def own(i: _Inst) -> Optional[str]:
        return stage_of(i.op_name) if i.op_name is not None else None

    def flow(name: str, k: Optional[int], seen: frozenset) -> Optional[str]:
        """Stage of the value ``name`` (of its tuple element ``k``),
        followed through the ops that only move it."""
        i = insts.get(name)
        if i is None or (name, k) in seen:
            return None
        seen = seen | {(name, k)}
        if own(i) is not None:
            return own(i)
        if i.opcode == "get-tuple-element":
            return flow(i.operands[0], i.index, seen)
        if i.opcode == "tuple":
            picks = [i.operands[k]] if k is not None and k < len(
                i.operands) else i.operands
            return next(filter(None, (flow(o, None, seen) for o in picks)),
                        None)
        if i.opcode == "parameter":
            c = caller.get(i.comp)
            if c is None:
                return None
            if c.opcode == "while":     # the last iteration's value, else
                body = roots.get(c.body)    # the loop's initial one
                return (flow(body, k, seen) if body else None) or (
                    flow(c.operands[0], k, seen) if c.operands else None)
            if i.index is not None and i.index < len(c.operands):
                return flow(c.operands[i.index], k, seen)
            return None
        if i.opcode == "while":
            body = roots.get(i.body)
            return flow(body, k, seen) if body else None
        if i.opcode in _MOVES:
            return flow(i.operands[0], None, seen) if i.operands else None
        if i.op_name is not None:
            return None                 # the search loop's own plumbing
        # made by the compiler: the stage of what it computes from, else of
        # the instruction whose computation holds it
        for o in i.operands:
            got = flow(o, None, seen)
            if got is not None:
                return got
        c = caller.get(i.comp)
        return own(c) if c is not None else None

    out = {}
    for name, i in insts.items():
        # an op the search loop's own code emitted (its op_name names no
        # stage) is plumbing, a loop op too; only a value passes through
        if own(i) is None and i.op_name is not None and (
                i.opcode not in _MOVES):
            continue
        got = flow(name, None, frozenset())
        if got is not None:
            out[name] = got
    return out


def _program_ops(tr: "tracing.Trace", dev: str, module: str) -> List:
    """The ops of one chip in the window that start inside an execution of
    ``module``."""
    mods, out, j = tr._modules(dev, module), [], 0
    for e in sorted(tr._ops[dev], key=lambda e: float(e[1])):
        s = float(e[1])
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        if j < len(mods) and mods[j][0] <= s:
            out.append(e)
    return out


def stage_self_ns(tr: "tracing.Trace", stages: Dict[str, str],
                  module: str) -> Dict[Optional[str], float]:
    """Self time of the program's ops per stage (None: no stage), summed
    over the traced steps and averaged over the chips."""
    per: Dict[Optional[str], float] = {}
    for dev in tr.devices:
        for name, ns in tracing.self_times(
                _program_ops(tr, dev, module)).items():
            key = stages.get(name)
            per[key] = per.get(key, 0.0) + ns
    k = max(tr.chips, 1)
    return {key: ns / k for key, ns in per.items()}


def load_spans(logdir: str, prefix: str = SPAN_PREFIX) -> List[List]:
    """Host events whose name starts with ``prefix`` in the one
    ``*.xplane.pb`` under ``logdir``: ``[name, start_ns, duration_ns,
    {stat: value}]``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, found "
                           f"{paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend([e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                       for e in line.events if e.name.startswith(prefix))
    return out


def span_ns(spans: Sequence, name: str, lo: float, hi: float) -> float:
    """Summed duration of the spans named ``name`` that start in
    [lo, hi)."""
    return sum(float(d) for n, s, d, *_ in spans
               if n == name and lo <= float(s) < hi)


def label(spans: Sequence[Tuple[float, float, str]], s: float,
          e: float) -> str:
    """The shortest host span among those with the largest overlap with
    [s, e] (to the nanosecond): the most specific phase the gap fell in."""
    best, key = tracing.NO_SPAN, None
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            k = (-round(ov), b - a)
            if key is None or k < key:
                best, key = name, k
    return best


def idle_gaps(tr: "tracing.Trace", spans: Sequence, n: int = 10
              ) -> List[Tuple[str, float]]:
    """``Trace.idle_gaps`` with each gap named by ``label`` over the
    benchmark's spans and the engine's."""
    gaps = []
    for busy in tr._busy.values():
        edges = [tr.lo] + [x for iv in busy for x in iv] + [tr.hi]
        gaps.extend((e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s)
    ivs = [(float(s), float(s) + float(d), name)
           for name, s, d, *_ in list(tr.host) + list(spans)]
    gaps.sort(reverse=True)
    return [(label(ivs, s, e), ns * 1e-9) for ns, s, e in gaps[:n]]
