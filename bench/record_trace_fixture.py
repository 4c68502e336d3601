#!/usr/bin/env python3
"""Record a small profiler trace on the chip for the trace-reduction tests.

    python bench/record_trace_fixture.py --out <dir>

Runs a few iterations of a jitted program that holds two Pallas kernels
(the packed-LNS matmul and the packed Madam update), with the benchmark's
host spans around the calls and a deliberate host wait between them, under
``jax.profiler.trace``. Writes the ``.xplane.pb`` to ``<dir>/fixture.xplane.pb``,
the compiled program's HLO to ``<dir>/fixture.hlo.txt`` and a readable dump
of the trace's planes, lines and first events to ``<dir>/structure.txt``.
The tests under ``tests/bench`` read the first two.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    import jax
    import jax.numpy as jnp

    from repro.core.lns import LNSFormat
    from repro.kernels import dispatch

    if jax.devices()[0].platform != "tpu":
        print("no TPU attached", file=sys.stderr)
        return 2
    fmt8 = LNSFormat(8, 8)
    fmt16 = LNSFormat(16, 2048)

    def prog(pa, pb, words, g, v, count):
        y = dispatch.qmatmul(pa, pb, fmt8)
        w2, v2 = dispatch.madam_step(words, g, v, count, fmt16, lr=2.0 ** -7)
        return y, w2, v2

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pa = jax.random.randint(k1, (200, 512), 0, 256).astype(jnp.uint8)
    pb = jax.random.randint(k2, (512, 384), 0, 256).astype(jnp.uint8)
    words = jax.random.randint(k3, (512, 1024), 0, 65536).astype(jnp.uint16)
    g = jax.random.normal(k4, (512, 1024), jnp.float32)
    v = jnp.zeros((512, 1024), jnp.float32)
    count = jnp.ones((), jnp.int32)
    fn = jax.jit(prog)
    compiled = fn.lower(pa, pb, words, g, v, count).compile()
    (out / "fixture.hlo.txt").write_text(compiled.as_text())
    jax.block_until_ready(fn(pa, pb, words, g, v, count))

    tdir = out / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(str(tdir)):
        for _ in range(args.iters):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                res = fn(pa, pb, words, g, v, count)
                jax.block_until_ready(res)
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(0.002)
    path = sorted(glob.glob(str(tdir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, out / "fixture.xplane.pb")

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        lines.append(f"PLANE {plane.name!r} stats="
                     f"{[(k, str(v)[:80]) for k, v in plane.stats]}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:12]:
                st = [(k, str(v)[:100]) for k, v in ev.stats]
                lines.append(f"    EV {ev.name!r} start_ns={ev.start_ns} "
                             f"dur_ns={ev.duration_ns} stats={st}")
    (out / "structure.txt").write_text("\n".join(lines) + "\n")
    print(f"wrote {out} ({os.path.getsize(out / 'fixture.xplane.pb')} B)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
