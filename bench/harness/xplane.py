"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a chip's
  ``XLA Ops`` line, inside the traced window, averaged over the chips;
* per-op device time: the summed durations of each operation's events;
* idle gaps: the intervals between busy stretches, each named by the
  innermost ``bench.*`` host span that covers its middle (the benchmark's
  own ``TraceAnnotation``s), ``host`` where none does; device times are
  first shifted so that no program run starts before the host call that
  dispatched it (the two clocks are synchronized to within a millisecond);
* module time: the summed durations of each compiled program's runs on
  the ``XLA Modules`` line.

The window is the host span ``bench.trace_window`` the harness opens around
what it traces; without one, the stretch from the first to the last device
operation.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# control-flow ops whose events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


@dataclasses.dataclass
class OpEvent:
    name: str           # HLO instruction name
    start: float        # seconds, trace clock
    dur: float
    module: str         # the program run whose interval holds the op
    device: str


_INSTR = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")


def instruction_name(event_name: str) -> str:
    """TPU traces name an op event by its HLO text, ``%fusion.3 = ...``;
    the instruction name is what precedes ``=``."""
    m = _INSTR.match(event_name.strip())
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Reduction:
    window: Interval
    busy_s: float                       # mean over chips
    devices: List[str]
    op_time: Dict[str, float]           # op name -> seconds (all chips)
    module_time: Dict[str, float]       # module name -> seconds
    module_runs: Dict[str, int]
    module_whole: Dict[str, List[float]]  # runs wholly inside the window
    ops: List[OpEvent]
    gaps: List[Tuple[str, float]]       # (host span, seconds), longest first

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def is_device_plane(name: str) -> bool:
    return bool(re.match(r"^/device:TPU:\d+$", name))


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: Interval, win: Interval) -> Optional[Interval]:
    s, e = max(iv[0], win[0]), min(iv[1], win[1])
    return (s, e) if e > s else None


def host_spans(planes) -> List[Tuple[str, float, float]]:
    """Every ``bench.*`` span on any host line: (name, start, end)."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def host_dispatches(planes) -> Dict[str, List[float]]:
    """Start times of the host's ``PjitFunction(<fn>)`` calls, by ``fn``."""
    out: Dict[str, List[float]] = defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                m = re.match(r"^PjitFunction\((.+)\)$", ev.name)
                if m:
                    out[m.group(1)].append(ev.start_ns * 1e-9)
    for v in out.values():
        v.sort()
    return out


def clock_shift(runs: List[Tuple[float, float, str]],
                dispatches: Dict[str, List[float]]) -> float:
    """Seconds to add to device times so that a program run does not
    appear to start before the host call that dispatched it: the median,
    over runs, of how far each run starts before its nearest dispatch of
    the same function (0 when runs start after them)."""
    deltas = []
    for s, _, name in runs:
        host = dispatches.get(re.sub(r"^jit_", "", name))
        if not host:
            continue
        i = bisect.bisect_left(host, s)
        near = [host[j] for j in (i - 1, i) if 0 <= j < len(host)]
        deltas.append(min((s - h for h in near), key=abs))
    if not deltas:
        return 0.0
    med = statistics.median(deltas)
    return -med if med < 0 else 0.0


def label(t: float, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost benchmark span (other than the window) around ``t``."""
    best, width = "host", float("inf")
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def reduce(planes, max_gaps: int = 10) -> Reduction:
    """``planes``: ``ProfileData(...).planes`` of one trace."""
    planes = list(planes)
    spans = host_spans(planes)
    dispatches = host_dispatches(planes)
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    ops: List[OpEvent] = []
    modules: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        if not is_device_plane(plane.name):
            continue
        devices.append(plane.name)
        runs = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    runs.append((s, s + ev.duration_ns * 1e-9,
                                 re.sub(r"\(\d+\)$", "", ev.name)))
        runs.sort()
        shift = clock_shift(runs, dispatches)
        runs = [(s + shift, e + shift, n) for s, e, n in runs]
        modules.extend((n, s, e) for s, e, n in runs)
        starts = [r[0] for r in runs]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                s = ev.start_ns * 1e-9 + shift
                i = bisect.bisect_right(starts, s) - 1
                mod = runs[i][2] if i >= 0 and s <= runs[i][1] else ""
                ops.append(OpEvent(instruction_name(ev.name), s,
                                   ev.duration_ns * 1e-9, mod, plane.name))
    if wins:
        win = (min(s for s, _ in wins), max(e for _, e in wins))
    elif ops:
        win = (min(o.start for o in ops), max(o.start + o.dur for o in ops))
    else:
        win = (0.0, 0.0)

    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    op_time: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ivs = []
        for o in ops:
            if o.device != dev:
                continue
            c = clip((o.start, o.start + o.dur), win)
            if c is None:
                continue
            ivs.append(c)
            if op_family(o.name) not in CONTAINERS:
                op_time[o.name] += c[1] - c[0]
        merged = union(ivs)
        busy_total += sum(e - s for s, e in merged)
        edges = [win[0]] + [x for iv in merged for x in iv] + [win[1]]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((label(0.5 * (s + e), spans), e - s))
    module_time: Dict[str, float] = defaultdict(float)
    module_runs: Dict[str, int] = defaultdict(int)
    module_whole: Dict[str, List[float]] = defaultdict(list)
    for name, s, e in modules:
        c = clip((s, e), win)
        if c is not None:
            module_time[name] += c[1] - c[0]
            module_runs[name] += 1
            if c == (s, e):
                module_whole[name].append(e - s)
    gaps.sort(key=lambda g: -g[1])
    n = max(len(devices), 1)
    return Reduction(window=win, busy_s=busy_total / n, devices=devices,
                     op_time=dict(op_time), module_time=dict(module_time),
                     module_runs=dict(module_runs),
                     module_whole=dict(module_whole), ops=ops,
                     gaps=gaps[:max_gaps])


def load(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)


def op_family(name: str) -> str:
    """An op's name without its numeric suffix (``fusion.12`` ->
    ``fusion``), for the breakdown's top list."""
    return re.sub(r"(\.\d+)+$", "", name)


def top_ops(red: Reduction, n: int = 10) -> List[List]:
    fam: Dict[str, float] = defaultdict(float)
    for name, t in red.op_time.items():
        fam[op_family(name)] += t
    return [[k, v] for k, v in sorted(fam.items(), key=lambda kv: -kv[1])[:n]]
