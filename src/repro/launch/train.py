"""End-to-end training driver (runs the smoke-scale configs on CPU; the
same code path drives TPU pods — only the mesh and config names change).

Sets the XLA latency-hiding-scheduler flags that overlap collectives with
compute on real TPWs before jax initializes, builds the LNS-native train
step under the logical sharding rules, and runs the fault-tolerant
supervisor loop with async checkpointing.

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
import os

# Comm/compute overlap knobs for real TPU runs (latency-hiding scheduler +
# async collective fusion). Harmless no-ops on the CPU backend.
TPU_PERF_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true"
)
if os.environ.get("REPRO_TPU_FLAGS"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + TPU_PERF_FLAGS)

import argparse
import math
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_rules, get_smoke_config
from repro.core.quantizer import QuantConfig
from repro.distributed.params_sharding import batch_shardings
from repro.distributed.sharding import shard_ctx
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.madam import MadamConfig
from repro.training import build_train_step, init_train_state
from repro.training.data import SyntheticLM
from repro.training.loop import SupervisorConfig, run_supervised


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU scale)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2.0 ** -7)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--format", default="lns8",
                    choices=["lns8", "fp8", "fp32"])
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(),
                                         "lns_madam_ckpt"))
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="collect per-layer numerics telemetry and export "
                         "a Chrome trace (train_step spans + numerics "
                         "counter tracks; opens in Perfetto) into DIR when "
                         "the run ends")
    ap.add_argument("--numerics-log", default=None, metavar="FILE",
                    help="structured jsonl step log (one line per step: "
                         "loss, wall time, per-layer LNS health)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace "
                         "(TensorBoard format) written to DIR, device ops "
                         "under the model's named scopes")
    ap.add_argument("--quiet", default=True,
                    type=lambda s: s.lower() not in ("0", "false", "no"),
                    metavar="BOOL",
                    help="--quiet=false prints a progress line every "
                         "--progress-every steps through the observer")
    ap.add_argument("--progress-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    qcfg = {"lns8": QuantConfig.lns_madam(), "fp8": QuantConfig.fp8(),
            "fp32": QuantConfig.full_precision()}[args.format]
    mcfg = MadamConfig(lr=args.lr)
    mesh = make_host_mesh(data=jax.device_count())
    rules = get_rules(args.arch)

    observer = None
    if args.trace_dir or args.numerics_log or not args.quiet:
        from repro.obs import NumericsObserver
        observer = NumericsObserver(log_path=args.numerics_log,
                                    quiet=args.quiet,
                                    progress_every=args.progress_every)

    with shard_ctx(mesh, rules):
        state = init_train_state(jax.random.PRNGKey(0), cfg, mcfg)
        n = sum(x.size for x in jax.tree.leaves(state.params))
        print(f"arch={cfg.name} params={n:,} format={args.format} "
              f"mesh={dict(mesh.shape)}")
        step_fn = jax.jit(build_train_step(
            cfg, qcfg, mcfg, accum_steps=args.accum_steps,
            numerics=observer is not None))
        data = SyntheticLM(cfg, batch=args.batch, seq=args.seq)
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)

        def put(b):
            b = jax.tree.map(jnp.asarray, b)
            sh = batch_shardings(b, mesh, rules)
            return jax.device_put(b, sh)

        def run():
            return run_supervised(
                step_fn, state, data, ckpt,
                SupervisorConfig(max_steps=args.steps,
                                 save_every=args.save_every),
                device_put_batch=put, observer=observer)

        t0 = time.monotonic()
        if args.jax_profile:
            with jax.profiler.trace(args.jax_profile):
                report = run()
        else:
            report = run()
        dt = time.monotonic() - t0
        tok = args.steps * args.batch * args.seq
        print(f"done: {report.steps_done} steps in {dt:.1f}s "
              f"({tok / dt:.0f} tok/s) loss {report.losses[0]:.4f} -> "
              f"{report.losses[-1]:.4f}; recovered={report.failures_recovered} "
              f"stragglers={report.straggler_events}")
        if observer is not None:
            summary = observer.summary()
            worst = summary.get("worst_sat_frac")
            if worst is not None:
                print(f"numerics: worst saturation {worst:.4f} "
                      f"({summary['worst_sat_site']}), update qerr mean "
                      f"{summary.get('update.qerr_rel_mean', 0):.2e}")
            if args.trace_dir:
                print("trace:", observer.export(args.trace_dir,
                                                tag=cfg.name))
            observer.close()
    # recovered failures are the supervisor's business; an unfinished run
    # or a non-finite loss it committed is the caller's, so it must fail
    bad = [i for i, x in enumerate(report.losses) if not math.isfinite(x)]
    if report.steps_done < args.steps or bad:
        raise SystemExit(
            f"training failed: {report.steps_done}/{args.steps} steps "
            f"done, non-finite loss at steps {bad}")


if __name__ == "__main__":
    main()
