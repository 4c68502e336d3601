#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does, and add what the program's own
spans, counters and scopes show.

    python bench/program_trace.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--out FILE]

The run, its result line (the last line of standard output) and its
information line are the harness's own. Standard error then carries one
more line, ``program {...}`` (also written to ``--out``):

* serving cells: the engine's phase counters since the warm-up
  (``phases``: count, total and longest seconds of each ``repro.engine.*``
  span) and its decode-row counter beside the harness's ``decode_tokens``
  over the same stretch of the window;
* with ``--trace 1``: the idle gaps named by the innermost benchmark or
  program span (``idle_gaps``), for serving the host self time per decode
  step (``engine_host_ms``, each ``repro.engine.step`` less its ``*.wait``
  children) beside the window's idle time, and per traced program the
  device milliseconds per run under each named scope (``device_scopes``,
  with ``attention/core`` apart, and each scope's largest op families);
  ``idle_by_span`` sums every idle gap by the span that names it.

The harness does not read these yet; the benchmark's own runs never run
this. The tool is temporary: it patches harness internals
(``runner.kernel_programs``, ``Tracer.reduce``, ``ServeRun.window``,
``ServeRun.host_counters``, ``ServeRun.free``) and compiles each traced
program a second time. Once the harness itself keeps the program's spans
and compiled op names (ROADMAP design debt 11), this file goes, with the
loops of ``harness/program.py`` that repeat ``xplane.reduce``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import runner  # noqa: E402

T_START = runner.process_start()


class Watch:
    """Taps on the harness's objects for one run; each records into
    ``self.out`` and leaves what the harness computes unchanged."""

    def __init__(self):
        self.out = {}
        self.texts = {}

    def install(self) -> None:
        from bench.harness import serve
        watch = self

        kernel_programs = runner.kernel_programs

        def programs(fn, args, make_jaxpr):
            from bench.harness import hlo
            text = fn.lower(*args).compile().as_text()
            watch.texts[hlo.module_name(text)] = text
            return kernel_programs(fn, args, make_jaxpr)
        runner.kernel_programs = programs

        reduce = runner.Tracer.reduce

        def tracer_reduce(tracer):
            from jax.profiler import ProfileData
            files = sorted(tracer.dir.rglob("*.xplane.pb"))
            data = ProfileData.from_file(str(files[-1])) if files else None
            red = reduce(tracer)
            if data is not None and red is not None:
                watch.data, watch.red = data, red
                watch.planes = list(data.planes)
            return red
        runner.Tracer.reduce = tracer_reduce

        window = serve.ServeRun.window

        def serve_window(run, seconds, tracer=None):
            eng = run.engine
            step = eng.step

            def counted_step(*a, **kw):
                # the window's base is set just before its first step
                if run._base and "decode_rows" not in run._base:
                    run._base["decode_rows"] = eng.decode_rows
                return step(*a, **kw)
            eng.step = counted_step
            try:
                return window(run, seconds, tracer)
            finally:
                del eng.step
        serve.ServeRun.window = serve_window

        host_counters = serve.ServeRun.host_counters

        def serve_host_counters(run):
            c = host_counters(run)
            watch.out["decode_tokens"] = c["decode_tokens"]
            watch.out["decode_steps"] = c["decode_steps"]
            watch.out["decode_rows"] = (run.engine.decode_rows
                                        - run._base["decode_rows"])
            return c
        serve.ServeRun.host_counters = serve_host_counters

        free = serve.ServeRun.free

        def serve_free(run):
            if run.engine is not None:
                watch.out["phases"] = run.engine.phase_stats()
            free(run)
        serve.ServeRun.free = serve_free

    def trace_view(self) -> None:
        """What the trace shows of the program's spans and scopes."""
        from bench.harness import program, xplane
        planes, red = getattr(self, "planes", None), getattr(self, "red",
                                                             None)
        if red is None:
            return
        pspans = [(n, s, e) for n, s, e, _ in program.program_spans(planes)]
        spans = xplane.host_spans(planes) + pspans
        gaps = program.named_gaps(red, spans, max_gaps=None)
        self.out["idle_gaps"] = gaps[:10]
        self.out["idle_by_span"] = program.idle_by_span(gaps)
        self.out["window_s"] = red.window_s
        self.out["idle_s"] = red.window_s - red.busy_s
        host = program.engine_host(pspans, red.window)
        if host is not None:
            self.out["engine_host_ms"], self.out["traced_decode_steps"] = host
        scopes = {}
        for module, text in self.texts.items():
            fams = program.scope_families(planes, red, module, text)
            if fams is None:
                continue
            per = {sc: sum(f.values()) for sc, f in fams.items()}
            total = sum(per.values())
            scopes[module] = {
                "ms_per_run": total,
                "scopes": per,
                "top_level": program.top_level(per),
                "unscoped_share": per.get(program.UNSCOPED, 0.0) / total
                if total else None,
                "families": {sc: dict(list(f.items())[:8])
                             for sc, f in fams.items()},
            }
        self.out["device_scopes"] = scopes


def main(argv) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--out", default=None)
    args, rest = ap.parse_known_args(argv)
    watch = Watch()
    watch.install()
    rc = runner.main(rest, ROOT, T_START)
    watch.trace_view()
    line = json.dumps(watch.out, default=float)
    print(f"program {line}", file=sys.stderr, flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
