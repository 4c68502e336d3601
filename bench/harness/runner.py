"""One run of one cell: set-up, window, optional trace, check, result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace
1`` its per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number the correctness check compared, with its
limit. The same numbers are the last lines on standard error. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits nonzero.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    pass


def process_start() -> float:
    """``time.monotonic()`` at which this process started (Linux ``/proc``;
    elsewhere, now)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100]."""
    v = sorted(values)
    if not v:
        return float("nan")
    x = (len(v) - 1) * q / 100.0
    lo = int(x)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


def prepare_environment(cell) -> None:
    """Flags that must be set before JAX starts a backend, and the compile
    cache inside the checkout (or where ``JAX_COMPILATION_CACHE_DIR``
    says)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    if cell.traffic["kind"] == "serve":
        from repro.launch.mesh import require_exact_rounding
        require_exact_rounding()
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    import jax

    from repro.kernels import dispatch
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    if dispatch.resolve_backend() != "pallas" or dispatch.resolve_interpret():
        raise NoChip("the kernel backend does not resolve to compiled "
                     "Pallas (is REPRO_KERNEL_BACKEND or "
                     "REPRO_KERNEL_INTERPRET set?)")
    return devs


def device_info(devs) -> Dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_stats(devs) -> Dict:
    """The fullest chip's ``memory_stats()`` as JAX reports them."""
    stats = [dv.memory_stats() or {} for dv in devs]
    return max(stats, key=lambda m: m.get("peak_bytes_in_use", 0))


def memory_peak(devs) -> Optional[int]:
    peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use") for dv in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def limits_of(root: Path, workload: str) -> Dict[str, float]:
    with open(root / "bench" / "limits" / f"{workload}.json") as f:
        return json.load(f)["limits"]


class Tracer:
    """Profile the first ``seconds`` of a window into a directory inside
    the checkout, inside a ``bench.trace_window`` span."""

    def __init__(self, root: Path, workload: str, seconds: float):
        self.dir = root / ".bench_traces" / workload
        self.seconds = seconds
        self.active = False
        self.done = False
        self._span = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # no Python function events: the benchmark's TraceAnnotation spans
        # and JAX's own host events are what gaps are named by, and tracing
        # every Python call would slow the serving loop it measures
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.trace_window")
        self._span.__enter__()
        self.active = True

    def tick(self, elapsed: float) -> None:
        if self.active and elapsed >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def reduce(self):
        from bench.harness import xplane
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            return None
        red = xplane.load(str(files[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


# ---------------------------------------------------------------------------


def run_train(cell, args, root: Path, devs, t_start: float, trace: bool,
              mesh=None, **job_kw) -> Dict:
    from bench.harness import train
    run = train.TrainRun(cell, args.seed, mesh=mesh, **job_kw)
    run.setup()
    programs = {}
    if trace:
        import jax
        import jax.numpy as jnp
        batch = run.job.batch_fn(run.words, jnp.int32(0))
        programs = kernel_programs(
            run.job.step_fn, (run.state, batch), jax.make_jaxpr)
    setup_s = time.monotonic() - t_start
    tracer = Tracer(root, cell.name, TRACE_SECONDS) if trace else None
    t_win = time.perf_counter()
    if tracer:
        tracer.start()
    win = run.window(args.seconds, on_step=lambda _: tracer and tracer.tick(
        time.perf_counter() - t_win))
    if tracer:
        tracer.stop()
    peak = memory_peak(devs)
    run.free()
    red = tracer.reduce() if tracer else None
    ref = run.reference_readings()
    numbers = train.compare(run.readings, ref)
    rec = {"kind": "train", "dims": run.job.dims,
           "trace": red, "programs": programs,
           "train": {"tokens": win["tokens"], "window_s": win["window_s"],
                     "seq": run.job.seq,
                     "tokens_per_step": run.job.tokens_per_step}}
    e2e = {"train_tokens_per_s": win["tokens"] / win["window_s"],
           "setup_s": setup_s}
    return {"numbers": numbers, "rec": rec, "e2e": e2e, "peak": peak,
            "attempted": run.steps_done, "failed": 0,
            "info": {"steps": win["steps"], "readings": run.readings,
                     "reference": ref}}


def run_serve(cell, args, root: Path, devs, t_start: float, trace: bool,
              mesh=None) -> Dict:
    from bench.harness import flops, serve
    run = serve.ServeRun(cell, args.seed, mesh=mesh)
    run.setup()
    programs = {}
    setup_s = time.monotonic() - t_start
    tracer = Tracer(root, cell.name, TRACE_SECONDS) if trace else None
    traced_flops = [0.0]
    dims = run.dims
    if tracer:
        # trace the window's last seconds: starting and stopping the
        # profiler stalls the host, so the host's own per-layer readings
        # come from the window before the trace starts
        t_trace = max(args.seconds - TRACE_SECONDS, 0.5 * args.seconds)

        def tick(elapsed):
            if not tracer.active and not tracer.done and elapsed >= t_trace:
                pre.update(run.host_counters())
                tracer.start()
                run.record_contexts = True
        pre = {}
        win = run.window(args.seconds, tracer=tick)
        tracer.stop()
        run.record_contexts = False
        traced_flops[0] = sum(flops.decode_flops(dims, c)
                              for c in run.decode_contexts)
        programs = decode_programs(run.engine)
    else:
        win = run.window(args.seconds)
        pre = run.host_counters()
    peak = memory_peak(devs)
    lat = run.latencies()
    c = dict(run.counters)
    c["decode_tokens"] = pre["decode_tokens"]
    c["host_decode_steps"] = pre["decode_steps"]
    window_reqs = run.window_requests()
    failed = sum(1 for rs, _, _ in window_reqs
                 if rs is not None and rs.finish_reason in ("error",
                                                            "aborted"))
    sample = run.check_sample()
    run.free()
    red = tracer.reduce() if tracer else None
    q = cell.config["serve"]["quant"]
    fmt = (q["bits"], q["gamma"])
    gaps = serve.served_gaps(run.words, sample, dims, fmt, fmt)
    served = sum(len(s["served"]) for s in sample)
    numbers = {"served_logit_gap": max(gaps) if gaps else float("inf"),
               "checked_tokens": served}
    e2e = {"serve_tokens_per_s": c["generated"] / win["window_s"],
           "tpot_p95_ms": 1e3 * percentile(lat["tpot"], 95),
           "setup_s": setup_s}
    rec = {"kind": "serve", "dims": dims, "trace": red, "programs": programs,
           "serve": {"counters": c, "traced_decode_flops": traced_flops[0]}}
    # the TTFT tail and the queue wait are recorded, not judged: runs of
    # one build spread too widely for a bound (PERF.md, section 2)
    return {"numbers": numbers, "rec": rec, "e2e": e2e, "peak": peak,
            "attempted": len(window_reqs), "failed": failed,
            "info": {"counters": c, "gaps": gaps,
                     "window_requests": len(window_reqs),
                     "ttft_p95_ms": 1e3 * percentile(lat["ttft"], 95),
                     "queue_wait_p50_ms": 1e3 * percentile(
                         lat["queue_wait"], 50),
                     "ttft_n": len(lat["ttft"]), "tpot_n": len(lat["tpot"])}}


def kernel_programs(fn, args, make_jaxpr) -> Dict[str, dict]:
    """``{HLO module name: kernel calls}`` of a jitted program at ``args``:
    the compiled text gives each call's instruction name and padded
    operands, the traced program their real shapes."""
    from bench.harness import hlo
    text = fn.lower(*args).compile().as_text()
    real = hlo.jaxpr_real_shapes(make_jaxpr(fn)(*args))
    return {hlo.module_name(text): hlo.apply_real_shapes(
        hlo.kernel_calls(text), real)}


def decode_programs(engine) -> Dict[str, dict]:
    """Kernel calls of the engine's decode step, at its current state and
    the arguments ``Engine.step`` passes."""
    import jax
    import jax.numpy as jnp
    batch = {"tokens": engine._put(engine._last_tok[:, None])}
    if engine.page_size:
        batch["block_tables"] = engine._put(engine._block_tables)
    pos = engine._put(engine._slot_len, jnp.int32)
    samp = {k: engine._put(v) for k, v in engine._samp.items()}
    with engine._ctx():
        return kernel_programs(engine._decode_fn, (
            engine.params, engine.caches, batch, pos, samp), jax.make_jaxpr)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the checks list: every limited number within its
    limit (a number without a limit is reported, not judged)."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return ok, checks


def execute(cell, args, root: Path, t_start: float, **run_kw) -> Dict:
    """Run the cell; returns the result object (the printed line)."""
    from bench.harness import flops, readers, xplane
    devs = require_chips(cell.chips)
    trace = bool(args.trace)
    kind = cell.traffic["kind"]
    fn = run_train if kind == "train" else run_serve
    out = fn(cell, args, root, devs, t_start, trace,
             mesh=cell.make_mesh(devs), **run_kw)
    limits = limits_of(root, cell.name)
    correct, checks = judge(out["numbers"], limits)
    info = device_info(devs)
    info["memory_peak_bytes"] = out["peak"]
    metrics = {}
    breakdown = None
    if trace:
        rec = out["rec"]
        rec["peaks"] = flops.peaks(info["kind"])
        for m in cell.metrics("per_layer"):
            v = readers.load(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = rec["trace"]
        if red is not None:
            info["busy_s"] = red.busy_s
            info["window_s"] = red.window_s
            breakdown = {"device_ops": xplane.top_ops(red),
                         "idle_gaps": [[n, s] for n, s in red.gaps]}
    else:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_info"] = dict(out["info"], memory_stats=memory_stats(devs))
    return result


def main(argv, root: Path, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (root / "src" / "repro").is_dir():
        print(f"no program beside the benchmark: {root / 'src' / 'repro'} "
              f"is missing", file=sys.stderr)
        return 2
    from bench.harness import spec
    cell = spec.load_cell(root, args.workload)
    prepare_environment(cell)
    try:
        result = execute(cell, args, root, t_start)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    info = result.pop("_info")
    print(json.dumps({"workload": cell.name, "seed": args.seed, **info},
                     default=float), file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
