"""Reduce a profiler trace to device op intervals, host spans and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with
``jax.profiler.ProfileData``: one list of ``Op`` per TPU (the plane
``/device:TPU:<n>``, line ``XLA Ops``) and the host spans the benchmark
opened with ``jax.profiler.TraceAnnotation``. On a v5e each op event is
named by its HLO instruction's text (``%fusion.44 = f32[19717]{0} fusion(
...)``); ``parse_hlo`` keeps the instruction's name and opcode. Control-flow
ops (``while``, ``conditional``, ``call``) span the ops of their bodies on
the same line and are dropped, or they would count a whole chunk as busy.
Everything else is arithmetic on intervals, kept apart so that tests can
drive it with hand-made events.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


class Op(NamedTuple):
    name: str         # HLO instruction name, e.g. "fusion.44"
    start: float      # ns
    end: float        # ns
    kind: str = ""    # HLO opcode, e.g. "fusion", "collective-permute-done"


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an op event named by its HLO text;
    a name that is not HLO text comes back as (name, "")."""
    if not text.startswith("%") or " = " not in text:
        return text, ""
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):                  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return name, rest.strip().split("(", 1)[0]


class Trace(NamedTuple):
    devices: Dict[int, List[Op]]    # device id -> ops, sorted by start
    spans: List[Op]                 # host spans of the benchmark, sorted
    window: Interval                # the traced window (ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(log_dir: str, span_prefix: str) -> Trace:
    """The trace under ``log_dir``; its window runs from the first host span
    whose name starts with ``span_prefix`` to the end of the last one, and
    only ops inside it are kept."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, kind = parse_hlo(e.name)
                    if kind not in CONTAINERS:
                        ops.append(Op(name, e.start_ns, e.end_ns, kind))
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                spans.extend(Op(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(span_prefix))
    if not spans:
        raise ValueError(f"no host span named {span_prefix}* in the trace")
    spans.sort(key=lambda s: s.start)
    window = (spans[0].start, max(s.end for s in spans))
    return Trace({d: clip(ops, window) for d, ops in devices.items()},
                 spans, window)


def clip(ops: Iterable[Op], window: Interval) -> List[Op]:
    lo, hi = window
    out = [o._replace(start=max(o.start, lo), end=min(o.end, hi))
           for o in ops if o.end > lo and o.start < hi]
    return sorted(out, key=lambda o: o.start)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def busy_ns(ops: Sequence[Op]) -> float:
    """Time in which at least one op runs."""
    return length((o.start, o.end) for o in ops)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def exposed_ns(ops: Sequence[Op], is_target) -> float:
    """Time in ops that satisfy ``is_target(op)`` while no other op runs."""
    target = [(o.start, o.end) for o in ops if is_target(o)]
    other = [(o.start, o.end) for o in ops if not is_target(o)]
    return length(subtract(target, other))


def op_seconds(ops: Sequence[Op], is_target) -> float:
    """Summed durations of the ops that satisfy ``is_target(op)``."""
    return sum(o.end - o.start for o in ops if is_target(o)) * 1e-9


def idle_gaps(ops: Sequence[Op], window: Interval, spans: Sequence[Op]
              ) -> List[Tuple[str, float]]:
    """Gaps of the window in which no op runs, each named by the innermost
    host span around its middle ("between spans" where there is none)."""
    gaps = subtract([window], [(o.start, o.end) for o in ops])
    named = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        around = [sp for sp in spans if sp.start <= mid <= sp.end]
        name = (min(around, key=lambda sp: sp.end - sp.start).name
                if around else "between spans")
        named.append((name, (e - s) * 1e-9))
    return named


def top(pairs: Iterable[Tuple[str, float]], n: int = 10
        ) -> List[Tuple[str, float]]:
    """The ``n`` largest (name, seconds) pairs after summing by name."""
    total: Dict[str, float] = {}
    for name, sec in pairs:
        total[name] = total.get(name, 0.0) + sec
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
