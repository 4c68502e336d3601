"""Request span tracing: a bounded ring of span events exportable as
Chrome trace-event JSON (loads in Perfetto / ``chrome://tracing``).

The engine records **complete** spans (a name, a start, a duration) and
**instant** events (preemption, requeue, abort, finish markers) into a
:class:`SpanRing`. Each request gets its own trace *thread* (tid = rid +
1; tid 0 is the engine itself), so Perfetto renders one swim-lane per
request with its queue -> prefill -> decode -> spec phases, and one lane
for the engine's step timeline.

Timestamps are engine-clock seconds (``Engine.now()``); the export
converts to the microsecond ``ts``/``dur`` fields the trace-event format
specifies. The ring is bounded (oldest events drop first) so a long-lived
server never grows without bound; ``dropped`` counts what fell out.

Appends happen on the engine/driver thread while exports may run on the
gateway's asyncio thread (``GET /obs/trace``), so the ring guards its
deque with a lock — the lock is only ever taken when tracing is enabled,
never on the disabled hot path.

The engine's own phases (admission, prefill, page growth, the decode
step's staging, launch and wait, token emission) go through
:class:`Phases`, the one span primitive: each span is a
``jax.profiler.TraceAnnotation`` named ``repro.<phase>`` (on the
profiler's host line, the clock the device trace is aligned to), an
always-on per-phase counter (count, total and longest seconds), and, when
an observer is attached, a complete span on the engine lane of the ring —
one clock read at each edge feeds all three.
"""
from __future__ import annotations

import json
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["SpanRing", "ENGINE_TID", "request_tid", "validate_chrome_trace",
           "Phases", "Span", "SPAN_PREFIX"]

ENGINE_TID = 0

# span/event categories — the validator keys off these
CAT_REQUEST = "request"
CAT_ENGINE = "engine"

# the request phases the acceptance bar requires for every completed
# request (spec spans additionally required when speculation ran)
REQUEST_PHASES = ("queue", "prefill", "decode")

# the profiler-trace name of a phase span is SPAN_PREFIX + its phase name
SPAN_PREFIX = "repro."


def request_tid(rid: int) -> int:
    """Trace thread id for request ``rid`` (tid 0 is the engine)."""
    return rid + 1


class SpanRing:
    """Bounded ring of trace events; export via :meth:`to_chrome`."""

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._events: "deque[tuple]" = deque(maxlen=capacity)
        self._tid_names: Dict[int, str] = {ENGINE_TID: "engine"}
        self._lock = threading.Lock()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._events)

    def _append(self, ev: tuple) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    def name_tid(self, tid: int, name: str) -> None:
        with self._lock:
            self._tid_names.setdefault(tid, name)

    def complete(self, name: str, cat: str, tid: int, t0: float,
                 t1: float, args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete span ``[t0, t1]`` (engine-clock seconds)."""
        self._append((name, cat, tid, t0, max(t1 - t0, 0.0), args))

    def instant(self, name: str, cat: str, tid: int, t: float,
                args: Optional[Dict[str, Any]] = None) -> None:
        self._append((name, cat, tid, t, None, args))

    def snapshot(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def to_chrome(self, extra_events: Optional[List[Dict[str, Any]]] = None
                  ) -> Dict[str, Any]:
        """The trace-event JSON document (``{"traceEvents": [...]}``)."""
        with self._lock:
            events = list(self._events)
            tid_names = dict(self._tid_names)
        out: List[Dict[str, Any]] = []
        for tid, name in sorted(tid_names.items()):
            out.append({"ph": "M", "name": "thread_name", "pid": 0,
                        "tid": tid, "args": {"name": name}})
        out.append({"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
                    "args": {"name": "repro serving engine"}})
        for name, cat, tid, t0, dur, args in sorted(
                events, key=lambda e: e[3]):
            ev: Dict[str, Any] = {"name": name, "cat": cat, "pid": 0,
                                  "tid": tid, "ts": t0 * 1e6}
            if dur is None:
                ev["ph"] = "i"
                ev["s"] = "t"  # thread-scoped instant
            else:
                ev["ph"] = "X"
                ev["dur"] = dur * 1e6
            if args:
                ev["args"] = args
            out.append(ev)
        if extra_events:
            out.extend(extra_events)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: str,
               extra_events: Optional[List[Dict[str, Any]]] = None) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(extra_events), f)
            f.write("\n")
        return path


# ---------------------------------------------------------------------------
# phase spans (the engine's step, on the profiler's clock)


class Span:
    """One phase interval; use through :meth:`Phases.span`. After the
    with-block, ``t0`` and ``t1`` hold its edges on the owner's clock."""

    __slots__ = ("_phases", "name", "args", "_ann", "t0", "t1")

    def __init__(self, phases: "Phases", name: str, args: Dict[str, Any]):
        self._phases = phases
        self.name = name
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(SPAN_PREFIX + self.name, **self.args)
        self._ann.__enter__()
        self.t0 = self._phases.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = self._phases.clock()
        self._ann.__exit__(*exc)
        self._phases._close(self)
        return False


class Phases:
    """Per-phase counters of one engine, and the span primitive feeding
    them. ``clock`` gives seconds (the engine clock, so ring spans line up
    with request spans); ``ring`` is the attached observer's span ring,
    or None. Single-writer: the thread that steps the engine."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.ring: Optional[SpanRing] = None
        self._rows: Dict[str, List[float]] = {}  # name -> [n, total, max]

    def span(self, name: str, **args) -> Span:
        """``with phases.span("engine.step"):`` — ``args`` (ints, floats,
        strings) become the profiler event's stats and the ring span's
        args."""
        return Span(self, name, args)

    def _close(self, sp: Span) -> None:
        d = sp.t1 - sp.t0
        row = self._rows.get(sp.name)
        if row is None:
            self._rows[sp.name] = [1, d, d]
        else:
            row[0] += 1
            row[1] += d
            if d > row[2]:
                row[2] = d
        if self.ring is not None:
            self.ring.complete(SPAN_PREFIX + sp.name, CAT_ENGINE, ENGINE_TID,
                               sp.t0, sp.t1, sp.args or None)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{phase: {"count", "total_s", "max_s"}}`` since the last
        :meth:`clear`."""
        return {name: {"count": int(n), "total_s": t, "max_s": m}
                for name, (n, t, m) in self._rows.items()}

    def clear(self) -> None:
        self._rows = {}


# ---------------------------------------------------------------------------
# schema validation (the CI round-trip check)


def _check_event(ev: Any, i: int) -> None:
    if not isinstance(ev, dict):
        raise ValueError(f"traceEvents[{i}] is not an object")
    for field in ("ph", "pid", "tid", "name"):
        if field not in ev:
            raise ValueError(f"traceEvents[{i}] missing {field!r}")
    ph = ev["ph"]
    if ph == "M":
        return  # metadata events carry no timestamp
    if "ts" not in ev or not isinstance(ev["ts"], (int, float)):
        raise ValueError(f"traceEvents[{i}] ({ph!r}) has no numeric ts")
    if ph == "X":
        if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
            raise ValueError(f"traceEvents[{i}] complete span has bad dur")
    elif ph not in ("i", "I", "C", "B", "E"):
        raise ValueError(f"traceEvents[{i}] unknown phase {ph!r}")


def validate_chrome_trace(doc: Dict[str, Any], *,
                          require_spec: bool = False
                          ) -> Dict[str, Dict[str, int]]:
    """Validate an exported trace document against the schema Perfetto
    needs plus the repo's own span contract.

    Structural checks: ``traceEvents`` is a list of well-formed events
    (phase, pid/tid, microsecond ``ts``, non-negative ``dur`` on complete
    spans). Semantic check: every request tid that carries a ``finish``
    marker with a completed reason (stop/length/capacity) must also carry
    queue, prefill, and decode spans — and a spec span when
    ``require_spec`` is set. Returns ``{rid: {span_name: count}}`` for
    the finished requests; raises ``ValueError`` on any violation.
    """
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise ValueError("trace document must hold a traceEvents list")
    events = doc["traceEvents"]
    spans: Dict[int, Dict[str, int]] = {}
    finished: Dict[int, str] = {}
    for i, ev in enumerate(events):
        _check_event(ev, i)
        if ev.get("cat") != CAT_REQUEST:
            continue
        tid = ev["tid"]
        if ev["ph"] == "X":
            per = spans.setdefault(tid, {})
            per[ev["name"]] = per.get(ev["name"], 0) + 1
        elif ev["ph"] == "i" and ev["name"] == "finish":
            reason = (ev.get("args") or {}).get("reason", "")
            if reason in ("stop", "length", "capacity"):
                finished[tid] = reason
    if not finished:
        raise ValueError("trace holds no completed request (finish marker "
                         "with reason stop/length/capacity)")
    required = REQUEST_PHASES + (("spec",) if require_spec else ())
    out: Dict[str, Dict[str, int]] = {}
    for tid, reason in sorted(finished.items()):
        per = spans.get(tid, {})
        missing = [name for name in required if not per.get(name)]
        if missing:
            raise ValueError(
                f"request tid {tid} finished ({reason}) but lacks "
                f"span(s) {missing}; has {sorted(per)}")
        out[tid - 1] = per  # keyed by rid
    return out
