#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload <name> [--batch N]

Prints the compiled programs' ``memory_analysis``: for a training cell the
donated train step at the traffic's batch (or ``--batch``), for a serving
cell the engine's decode step and its largest prefill bucket at the
configured slots and pages. Used to size the training batch and the page
pool against the chip's 16 GB; it runs nothing and times nothing.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fmt_mem(m) -> str:
    gb = 1e9
    tot = (m.argument_size_in_bytes + m.output_size_in_bytes
           - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return (f"args={m.argument_size_in_bytes / gb:.3f}GB "
            f"out={m.output_size_in_bytes / gb:.3f}GB "
            f"alias={m.alias_size_in_bytes / gb:.3f}GB "
            f"temp={m.temp_size_in_bytes / gb:.3f}GB "
            f"code={m.generated_code_size_in_bytes / 1e6:.1f}MB "
            f"total={tot / gb:.3f}GB")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--num-pages", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import spec
    from repro.kernels import dispatch

    cell = spec.load_cell(ROOT, args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    with dispatch.configured(backend="pallas", interpret=False):
        if cell.traffic["kind"] == "train":
            from bench.harness import train
            batch = args.batch or cell.traffic["batch"]
            import numpy as np
            from jax.sharding import AxisType, Mesh
            mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                        ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
            job = train.TrainJob(cell, batch=batch, mesh=mesh)
            state = place(jax.eval_shape(job.make_state,
                                         jnp.zeros((2,), jnp.uint32)))
            toks = place(jax.eval_shape(job.make_batch,
                                        jnp.zeros((2,), jnp.uint32),
                                        jnp.zeros((), jnp.int32)))
            t0 = time.perf_counter()
            c = job.step_fn.lower(state, toks).compile()
            print(f"train step batch={batch} seq={job.seq} compile_s="
                  f"{time.perf_counter() - t0:.1f} {fmt_mem(c.memory_analysis())}",
                  flush=True)
        else:
            from bench.harness import serve
            eng_kw = dict(cell.config["serve"]["engine"])
            if args.num_pages:
                eng_kw["num_pages"] = args.num_pages
            for name, c in serve.rehearse(cell, eng_kw, place):
                print(f"{name} {fmt_mem(c.memory_analysis())}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
