#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the sound program, its
control and its faults, over many seeds in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --out DIR \
        [--variants program,control,half_batch] [--seconds 10]

Training cells (no measured window): per seed, the first three steps of the
program's step against the plain reference. Variants:

* ``program``: the cell as configured;
* ``control``: the program's own lower-precision path, 8-bit update words
  (the configuration states 16);
* ``half_batch``: the step fed the first half of each batch, its loss the
  mean over that half.

Serving cells: per seed, a window of ``--seconds`` at the cell's own load,
then the served tokens of the check sample against the reference
(``program``) and, on the same sample, the gap of the token a 4-bit
reference puts first (``control``).

Each reading is one JSON line in ``DIR/<workload>.jsonl``; the benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_SERVE = (4, 1)       # LNSFormat(8, 8).with_bits(4): 4-bit words, gamma 1


def half_batch_builder(cfg, qcfg, mcfg):
    from repro.training.steps import build_train_step
    step = build_train_step(cfg, qcfg, mcfg)

    def half(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return half


def calibrate_train(cell, seeds, variants, emit, mesh):
    from bench.harness import train
    jobs = {}
    for v in variants:
        if v == "control":
            jobs[v] = train.TrainJob(cell, update_bits=8, mesh=mesh)
        elif v == "half_batch":
            saved = train.build_step
            train.build_step = half_batch_builder
            try:
                jobs[v] = train.TrainJob(cell, mesh=mesh)
            finally:
                train.build_step = saved
        else:
            jobs[v] = train.TrainJob(cell, mesh=mesh)
    for seed in seeds:
        ref = None
        for v, job in jobs.items():
            t0 = time.perf_counter()
            run = train.TrainRun(cell, seed, job=job)
            run.setup()
            run.free()
            if ref is None:
                ref = train.TrainRun(cell, seed,
                                     job=jobs.get("program", job)
                                     ).reference_readings()
            nums = train.compare(run.readings, ref)
            emit({"workload": cell.name, "variant": v, "seed": seed,
                  **nums, "seconds": time.perf_counter() - t0,
                  "program": run.readings, "reference": ref})


def calibrate_serve(cell, seeds, variants, seconds, emit, mesh):
    from bench.harness import serve
    engine = None
    for seed in seeds:
        t0 = time.perf_counter()
        run = serve.ServeRun(cell, seed, mesh=mesh)
        run.setup(engine)
        engine = run.engine
        run.window(seconds)
        sample = run.check_sample()
        counters = dict(run.counters)
        engine.params = None
        engine.caches = None
        q = cell.config["serve"]["quant"]
        fmt = (q["bits"], q["gamma"])
        row = {"workload": cell.name, "seed": seed, "counters": counters,
               "checked_tokens": sum(len(s["served"]) for s in sample),
               "checked_requests": len(sample)}
        if "program" in variants:
            gaps = serve.served_gaps(run.words, sample, run.dims, fmt, fmt)
            emit({**row, "variant": "program",
                  "served_logit_gap": max(gaps), "gaps": gaps,
                  "seconds": time.perf_counter() - t0})
        if "control" in variants:
            gaps = serve.served_gaps(run.words, sample, run.dims, fmt, fmt,
                                     control=CONTROL_SERVE)
            emit({**row, "variant": "control",
                  "served_logit_gap": max(gaps), "gaps": gaps,
                  "seconds": time.perf_counter() - t0})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True,
                    help="directory for <workload>.jsonl")
    args = ap.parse_args()
    from bench.harness import runner, spec
    cell = spec.load_cell(ROOT, args.workload)
    runner.prepare_environment(cell)
    mesh = cell.make_mesh(runner.require_chips(cell.chips))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{cell.name}.jsonl"
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = args.variants.split(",")

    def emit(row):
        with open(path, "a") as f:
            f.write(json.dumps(row, default=float) + "\n")
        brief = {k: row[k] for k in row if k not in ("program", "reference",
                                                     "gaps")}
        print(json.dumps(brief, default=float), flush=True)

    if cell.traffic["kind"] == "train":
        calibrate_train(cell, seeds, variants, emit, mesh)
    else:
        calibrate_serve(cell, seeds, variants, args.seconds, emit, mesh)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
