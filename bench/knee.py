#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate once, to find its knee.

    python bench/knee.py --workload <name> --rates 10,20,40 [--seconds 20]

One process, one engine: for each rate the cell's traffic runs a window of
``--seconds`` at that rate (lead-in included) and prints one JSON line:
offered and completed tokens per second, the latency tails, and the
backlog (requests due but not yet admitted) at the window's middle and at
its close. The knee is the highest rate whose backlog does not grow over
the window;
a cell's traffic file then fixes its rate below it. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog_at(run, now: float) -> int:
    """Requests due by ``now`` (engine clock) that were not yet admitted."""
    from bench.harness import traffic
    e_due = run.t_open - traffic.window_start(run.cell.traffic)
    return sum(1 for p in run.plan
               if e_due + p.due <= now and p.rid not in run.states)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    from bench.harness import runner, serve, spec
    cell = spec.load_cell(ROOT, args.workload)
    runner.prepare_environment(cell)
    runner.require_chips(cell.chips)
    engine = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate=rate)
        run = serve.ServeRun(cell, args.seed)
        run.setup(engine)
        engine = run.engine
        mid = {}

        def tick(elapsed, run=run, mid=mid):
            if not mid and elapsed >= args.seconds / 2:
                mid["backlog"] = backlog_at(run, run.t_open + elapsed)
        run.window(args.seconds, tracer=tick)
        lat = run.latencies()
        reqs = run.window_requests()
        backlog = sum(1 for rs, _, _ in reqs if rs is None)
        unfinished = sum(1 for rs, _, _ in reqs
                         if rs is not None and rs.t_finish is None)
        win = run.t_close - run.t_open
        print(json.dumps({
            "rate": rate, "due": len(reqs),
            "backlog_at_middle": mid.get("backlog"),
            "backlog_at_close": backlog,
            "running_at_close": unfinished,
            "tokens_per_s": run.counters["generated"] / win,
            "ttft_p50_ms": 1e3 * runner.percentile(lat["ttft"], 50),
            "ttft_p95_ms": 1e3 * runner.percentile(lat["ttft"], 95),
            "tpot_p95_ms": 1e3 * runner.percentile(lat["tpot"], 95),
            "queue_wait_p50_ms": 1e3 * statistics.median(lat["queue_wait"]),
            "decode_steps_per_s": run.counters["decode_steps"] / win,
            "preemptions": run.counters["preemptions"],
            "compiles_in_window": run.counters["compiles_in_window"]}),
            flush=True)
        engine.params = None
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
