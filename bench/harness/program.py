"""The program's own spans and scopes, read from a profiler trace.

* Phase spans: the serving engine opens a ``jax.profiler.TraceAnnotation``
  named ``repro.<phase>`` around each phase of ``Engine.step`` (admission,
  prefill, page growth, the decode step's staging, launch and wait, token
  emission; ``repro.obs.spans.Phases``), every phase a child of
  ``repro.engine.step``. They sit on the profiler's host line, the clock
  ``xplane`` aligns device ops to, so an idle gap of the chip can be named
  by the innermost span around it, the program's or the benchmark's.
* Scopes: ``jax.named_scope`` in the model and the step builders puts a
  scope path into every compiled instruction's ``op_name``
  (``jit(train_step)/transpose(jvp(attention))/core/dot_general``); the
  device trace names each op by its instruction, so the compiled text maps
  device time to scopes. A fusion whose own ``op_name`` has no scope takes
  the scope most of its fused computation's instructions carry (the scan's
  residual writes carry none and stay ``unscoped``).

A program without these spans and scopes reads as nothing here (None, or
every op ``unscoped``), never as an error.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from bench.harness import hlo, xplane

PREFIX = "repro."
STEP = "repro.engine.step"
DECODE_WAIT = "repro.engine.decode.wait"
WAIT_SUFFIX = ".wait"
# the model's and the step builders' top-level scopes; "core" and
# "kv_write" nest inside "attention"
TOP_SCOPES = ("embed", "attention", "mlp", "head", "grad_quantize",
              "optimizer", "sample")
UNSCOPED = "unscoped"

Span = Tuple[str, float, float]

_WRAPPED = re.compile(r"^[\w\-]*\((.*)\)$")
_INSTR = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")


# ---------------------------------------------------------------------------
# host spans


def program_spans(planes) -> List[Tuple[str, float, float, dict]]:
    """Every ``repro.*`` span on any host line: (name, start, end, args),
    in seconds on the trace clock, sorted by start."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    out.append((ev.name, s, s + ev.duration_ns * 1e-9,
                                dict(ev.stats)))
    out.sort(key=lambda x: x[1])
    return out


def named_gaps(red: xplane.Reduction, spans: List[Span],
               max_gaps: Optional[int] = 10) -> List[Tuple[str, float]]:
    """The reduction's idle gaps, longest first (``max_gaps`` of them, or
    all with None), each named by the innermost of ``spans`` (the
    benchmark's and the program's) around its middle: the gaps
    ``xplane.reduce`` finds, from the same op intervals in the same
    window."""
    gaps = []
    for dev in red.devices:
        ivs = [c for c in (xplane.clip((o.start, o.start + o.dur), red.window)
                           for o in red.ops if o.device == dev) if c]
        merged = xplane.union(ivs)
        edges = [red.window[0]] + [x for iv in merged for x in iv] \
            + [red.window[1]]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((xplane.label(0.5 * (s + e), spans), e - s))
    gaps.sort(key=lambda g: -g[1])
    return gaps[:max_gaps]


def _covered(ivs: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in xplane.union(ivs))


def engine_host(spans: List[Span], window: Tuple[float, float]
                ) -> Optional[Tuple[float, int]]:
    """(mean ms, steps) of host self time per decode step: for each
    ``repro.engine.step`` wholly inside ``window`` that holds a
    ``repro.engine.decode.wait``, its duration less the time its ``*.wait``
    descendants cover (the host blocked on the chip). None when no such
    step is in the window."""
    steps = [(s, e) for n, s, e in spans
             if n == STEP and window[0] <= s and e <= window[1]]
    waits = sorted((s, e, n) for n, s, e in spans if n.endswith(WAIT_SUFFIX))
    starts = [w[0] for w in waits]
    host = []
    for s, e in steps:
        inside = [w for w in waits[bisect.bisect_left(starts, s):
                                   bisect.bisect_right(starts, e)]
                  if w[1] <= e]
        if any(n == DECODE_WAIT for _, _, n in inside):
            host.append((e - s) - _covered([(a, b) for a, b, _ in inside]))
    if not host:
        return None
    return 1e3 * statistics.mean(host), len(host)


# ---------------------------------------------------------------------------
# device scopes


def scope_path(op_name: str) -> List[str]:
    """The components of an ``op_name`` with transform wrappers removed:
    ``transpose(jvp(attention))`` -> ``attention``, ``jit(f)`` -> ``f``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The top-level scope of an op (``attention/core`` and
    ``attention/kv_write`` for the parts nested in attention), or None. An
    instruction XLA merged from several ops names them all, joined by
    ``;``: the first that has a scope gives it."""
    for name in op_name.split(";"):
        path = scope_path(name)
        for i, part in enumerate(path):
            if part in TOP_SCOPES:
                if part == "attention":
                    for sub in ("core", "kv_write"):
                        if sub in path[i + 1:]:
                            return f"attention/{sub}"
                return part
    return None


def computations(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: computation}``: a computation opens with an
    unindented line that ends in ``{`` and closes with ``}``."""
    out, comp = {}, None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
            words = line.split()
            comp = (words[1] if words[0] == "ENTRY" else words[0]).lstrip("%")
        elif line.startswith("}"):
            comp = None
        else:
            m = _INSTR.match(line)
            if m and comp is not None:
                out[m.group(1)] = comp
    return out


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: scope or None}`` of a compiled program. A
    fusion whose own ``op_name`` has no scope takes the scope most of its
    fused computation's instructions carry; an instruction with an
    ``op_name`` outside every scope votes for none."""
    instrs = hlo.parse(hlo_text)
    comp_of = computations(hlo_text)
    votes: Dict[str, Counter] = defaultdict(Counter)
    for name, rec in instrs.items():
        if ":" not in name and rec["op_name"] and name in comp_of:
            votes[comp_of[name]][scope_of(rec["op_name"])] += 1
    out = {}
    for name, rec in instrs.items():
        if ":" in name:
            continue
        sc = scope_of(rec["op_name"])
        if sc is None and rec["opcode"] == "fusion" and rec["calls"]:
            v = votes.get(rec["calls"])
            if v:
                sc = v.most_common(1)[0][0]
        out[name] = sc
    return out


def module_runs(planes, red: xplane.Reduction
                ) -> List[Tuple[str, str, float, float]]:
    """(device, module, start, end) of every program run wholly inside the
    window, on the reduction's shifted device clock."""
    dispatches = xplane.host_dispatches(planes)
    out = []
    for plane in planes:
        if not xplane.is_device_plane(plane.name):
            continue
        runs = []
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    runs.append((s, s + ev.duration_ns * 1e-9,
                                 re.sub(r"\(\d+\)$", "", ev.name)))
        runs.sort()
        shift = xplane.clock_shift(runs, dispatches)
        for s, e, n in runs:
            s, e = s + shift, e + shift
            if red.window[0] <= s and e <= red.window[1]:
                out.append((plane.name, n, s, e))
    return out


def whole_run_ops(planes, red: xplane.Reduction, module: str
                  ) -> Tuple[List[xplane.OpEvent], int]:
    """The ops of the runs of ``module`` held whole in the window, less
    control-flow containers (``while``; as in ``xplane``'s op times), and
    the number of those runs."""
    runs: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for d, n, s, e in module_runs(planes, red):
        if n == module:
            runs[d].append((s, e))
    ops = []
    for o in red.ops:
        ivs = runs.get(o.device)
        if (not ivs or o.module != module
                or xplane.op_family(o.name) in xplane.CONTAINERS):
            continue
        i = bisect.bisect_right(ivs, (o.start, float("inf"))) - 1
        if i >= 0 and o.start <= ivs[i][1]:
            ops.append(o)
    return ops, sum(len(v) for v in runs.values())


def scope_families(planes, red: xplane.Reduction, module: str,
                   hlo_text: str) -> Optional[Dict[str, Dict[str, float]]]:
    """Device ms per run of ``module`` under each scope and op family
    (``fusion``, ``copy``, ...), over the runs held whole in the window;
    ``unscoped`` holds the ops no scope claims. None without a whole
    run."""
    ops, n = whole_run_ops(planes, red, module)
    if not n:
        return None
    scopes = instruction_scopes(hlo_text)
    total: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for o in ops:
        total[scopes.get(o.name) or UNSCOPED][xplane.op_family(o.name)] += \
            o.dur
    return {sc: {f: 1e3 * t / n
                 for f, t in sorted(fam.items(), key=lambda kv: -kv[1])}
            for sc, fam in sorted(total.items())}


def device_scopes(planes, red: xplane.Reduction, module: str,
                  hlo_text: str) -> Optional[Dict[str, float]]:
    """Device ms per run of ``module`` under each scope (``unscoped`` for
    the rest), over the runs held whole in the window; None without one."""
    fams = scope_families(planes, red, module, hlo_text)
    if fams is None:
        return None
    return {sc: sum(fam.values()) for sc, fam in fams.items()}


def idle_by_span(gaps: List[Tuple[str, float]]) -> Dict[str, float]:
    """Idle seconds summed by the span that names each gap, largest
    first (``named_gaps`` with no limit gives every gap)."""
    out: Dict[str, float] = defaultdict(float)
    for name, g in gaps:
        out[name] += g
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def top_level(scopes: Dict[str, float]) -> Dict[str, float]:
    """``device_scopes`` folded to the top-level scopes (``attention``
    holding ``attention/core`` and ``attention/kv_write``)."""
    out: Dict[str, float] = defaultdict(float)
    for k, v in scopes.items():
        out[k.split("/")[0]] += v
    return dict(out)
