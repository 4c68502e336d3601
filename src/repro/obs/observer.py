"""The engine-facing bundle: span ring + step timeline behind one handle.

``Engine`` holds ``self.observer`` (default ``None``); every hook site in
the hot loop is a single ``if obs is not None`` — the disabled path costs
one attribute load and a branch, no allocation. When an
:class:`EngineObserver` is attached, the engine calls these methods at
its lifecycle edges and the observer turns them into spans (per-request
swim-lanes) and timeline rows (per-step gauges).

All timestamps are engine-clock seconds (``Engine.now()``). Caveat:
simulated-time replay (``step(now=...)``) stamps request events with the
*caller's* clock while step walltimes come from the real engine clock —
span durations from such runs are degenerate, so attach observers to
real-time runs (``Engine.run()``, the online driver).
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.obs.spans import CAT_REQUEST, SpanRing, request_tid
from repro.obs.timeline import StepTimeline

__all__ = ["EngineObserver"]


class EngineObserver:
    """Collects request spans + step timeline for one engine."""

    def __init__(self, *, span_capacity: int = 65536,
                 timeline_capacity: int = 16384):
        self.spans = SpanRing(span_capacity)
        self.timeline = StepTimeline(timeline_capacity)
        self.started_at = time.time()  # wall time, for export filenames

    # -- request lifecycle hooks (engine thread) -----------------------

    def admitted(self, rs, *, resumed: bool = False) -> None:
        req = rs.request
        tid = request_tid(req.rid)
        self.spans.name_tid(tid, f"req {req.rid}")
        if resumed:
            self.spans.instant("resume", CAT_REQUEST, tid, rs.t_admit,
                               {"generated": len(rs.generated)})
        else:
            self.spans.complete("queue", CAT_REQUEST, tid,
                                req.arrival, rs.t_admit)

    def prefill(self, rs, t0: float, t1: float, *,
                gauges: Optional[Dict[str, int]] = None) -> None:
        req = rs.request
        tid = request_tid(req.rid)
        self.spans.complete("prefill", CAT_REQUEST, tid, t0, t1,
                            {"prompt_len": req.prompt_len})
        self.timeline.record("prefill", t0, t1, emitted=1,
                             **(gauges or {}))

    def preempted(self, rs, t: float) -> None:
        self.spans.instant("preempt", CAT_REQUEST,
                           request_tid(rs.request.rid), t,
                           {"generated": len(rs.generated)})

    def aborted_queued(self, rid: int, t: float) -> None:
        tid = request_tid(rid)
        self.spans.name_tid(tid, f"req {rid}")
        self.spans.instant("finish", CAT_REQUEST, tid, t,
                           {"reason": "aborted", "queued": True})

    def finished(self, rs, reason: str) -> None:
        tid = request_tid(rs.request.rid)
        if rs.t_first_token is not None and rs.t_finish is not None:
            self.spans.complete("decode", CAT_REQUEST, tid,
                                rs.t_first_token, rs.t_finish)
        self.spans.instant("finish", CAT_REQUEST, tid,
                           rs.t_finish if rs.t_finish is not None else 0.0,
                           {"reason": reason, "tokens": len(rs.generated)})

    # -- step hooks ----------------------------------------------------

    def decode_step(self, t0: float, t1: float, *, emitted: int,
                    gauges: Optional[Dict[str, int]] = None) -> None:
        self.timeline.record("decode", t0, t1, emitted=emitted,
                             **(gauges or {}))

    def spec_cycle(self, t0: float, t1: float, *, k: int,
                   rows: List[Tuple[int, int, int]], emitted: int,
                   gauges: Optional[Dict[str, int]] = None) -> None:
        """``rows`` is ``[(rid, accepted, emitted_for_request), ...]`` for
        the live slots the cycle covered."""
        accepted = 0
        for rid, acc, emit in rows:
            accepted += acc
            self.spans.complete("spec", CAT_REQUEST, request_tid(rid),
                                t0, t1, {"k": k, "accepted": acc,
                                         "emitted": emit})
        self.timeline.record("spec", t0, t1, emitted=emitted,
                             drafted=k * len(rows), accepted=accepted,
                             **(gauges or {}))

    # -- consumption ---------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        out = self.timeline.summary()
        out["span_events"] = len(self.spans)
        out["span_dropped"] = self.spans.dropped
        return out

    def time_breakdown(self, phases: Dict[str, Dict[str, float]],
                       wall_s: Optional[float] = None) -> Dict[str, float]:
        """Where a run's time went. ``phases`` is the engine's
        ``phase_stats()`` over the same run: ``wait_s`` is the host blocked
        on the device (the summed ``*.wait`` spans) and ``host_s`` the rest
        of ``engine.step``, host work no device result held up. With
        ``wall_s`` (the run's total wall), ``outside_s`` is the time outside
        ``engine.step`` (waiting for arrivals, the caller's loop) and the
        shares are over ``wall_s``; else over the steps' total. The
        timeline adds the walltime of prefill, decode and spec work."""
        s = self.timeline.summary()
        step = phases.get("engine.step", {}).get("total_s", 0.0)
        wait = sum(v["total_s"] for k, v in phases.items()
                   if k.endswith(".wait"))
        out = {
            "prefill_s": s.get("prefill_time_s", 0.0),
            "decode_s": s.get("decode_time_s", 0.0),
            "spec_s": s.get("spec_time_s", 0.0),
            "step_s": step,
            "wait_s": wait,
            "host_s": max(step - wait, 0.0),
        }
        if wall_s is not None:
            out["wall_s"] = wall_s
            out["outside_s"] = max(wall_s - step, 0.0)
        total = wall_s if wall_s else step
        if total > 0:
            for key in ("wait", "host", "outside"):
                if f"{key}_s" in out:
                    out[f"{key}_share"] = round(out[f"{key}_s"] / total, 4)
        return out

    def to_chrome(self) -> Dict[str, Any]:
        return self.spans.to_chrome(
            extra_events=self.timeline.to_chrome_counters())

    def export(self, trace_dir: str, *, tag: str = "trace") -> str:
        """Write the Chrome trace plus the timeline summary next to it;
        returns the trace path."""
        os.makedirs(trace_dir, exist_ok=True)
        stamp = time.strftime("%Y%m%d-%H%M%S",
                              time.localtime(self.started_at))
        path = os.path.join(trace_dir, f"{tag}-{stamp}.trace.json")
        self.spans.export(path,
                          extra_events=self.timeline.to_chrome_counters())
        with open(os.path.join(
                trace_dir, f"{tag}-{stamp}.timeline.json"), "w") as f:
            json.dump(self.summary(), f, indent=2, sort_keys=True)
            f.write("\n")
        return path

    def clear(self) -> None:
        self.spans.clear()
        self.timeline.clear()
