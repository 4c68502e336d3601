import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above run before ANY other import — jax locks the device count
at first init, and the production meshes need 512 placeholder host
devices. The CPU platform is pinned too: this is a host-side placeholder
tool, and neither it nor its ``--all`` children may take an attached
accelerator (a chip belongs to one process at a time). Smoke tests and
benchmarks never import this module, so they see the real single CPU
device.

For each cell the step function (train / prefill / decode per the shape's
kind) is jitted with explicit in_shardings from the logical rules, lowered
against ShapeDtypeStruct stand-ins (no allocation), compiled, and the
compiled artifact is mined for:

  * ``memory_analysis()``  — per-chip bytes: proves the cell fits (or not)
  * ``cost_analysis()``    — per-chip HLO FLOPs / bytes for §Roofline
  * partitioned HLO text   — collective operand bytes for §Roofline

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out experiments/dryrun
"""

import argparse
import json
import math
import subprocess
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells, get_config, get_rules, input_specs
from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig
from repro.distributed.params_sharding import (batch_shardings,
                                               cache_logical_axes,
                                               params_logical_axes,
                                               opt_logical_axes,
                                               tree_shardings)
from repro.distributed.sharding import shard_ctx
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.models.common import ArchConfig
from repro.models.model import init_caches
from repro.optim.madam import MadamConfig
from repro.training.steps import (build_decode_step, build_prefill_step,
                                  build_train_step, init_train_state)

SERVE_FMT = LNSFormat(bits=8, gamma=8)  # inference weights: packed 8-bit LNS


def _mesh_batch_div(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _memory_dict(compiled) -> Dict[str, float]:
    try:
        m = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = float(v)
    out["peak_bytes"] = (out.get("argument_size_in_bytes", 0)
                         + out.get("output_size_in_bytes", 0)
                         + out.get("temp_size_in_bytes", 0)
                         - out.get("alias_size_in_bytes", 0))
    return out


def _lower_compile(cfg, spec, mesh, rules, *, accum_steps=1, scan_unroll,
                   save_hlo=None, remat=True):
    """Lower + compile one step function; return (cost, mem, hlo, times)."""
    t0 = time.monotonic()
    with shard_ctx(mesh, rules):
        batch_specs = input_specs(cfg, spec.name)
        batch_sh = batch_shardings(batch_specs, mesh, rules)
        qcfg = QuantConfig.lns_madam()

        if spec.kind == "train":
            mcfg = MadamConfig(factored=(cfg.family == "moe"))
            state_shape = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, mcfg))
            st_axes = type(state_shape)(
                params=params_logical_axes(state_shape.params),
                opt=opt_logical_axes(state_shape.params, state_shape.opt),
                step=(),
            )
            state_sh = tree_shardings(st_axes, mesh, rules)
            step = build_train_step(cfg, qcfg, mcfg, accum_steps=accum_steps,
                                    scan_unroll=scan_unroll, remat=remat)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shape, batch_specs)
        elif spec.kind == "prefill":
            mcfg = MadamConfig(update_format=SERVE_FMT)
            state_shape = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, mcfg))
            params_sh = tree_shardings(
                params_logical_axes(state_shape.params), mesh, rules)
            step = build_prefill_step(cfg, qcfg, mcfg,
                                      scan_unroll=scan_unroll)
            jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(state_shape.params, batch_specs)
        else:  # decode
            mcfg = MadamConfig(update_format=SERVE_FMT)
            state_shape = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, mcfg))
            params_sh = tree_shardings(
                params_logical_axes(state_shape.params), mesh, rules)
            cache_shape = jax.eval_shape(
                lambda: init_caches(spec.global_batch, spec.seq_len, cfg))
            cache_sh = tree_shardings(
                cache_logical_axes(cache_shape), mesh, rules)
            step = build_decode_step(cfg, qcfg, mcfg,
                                     scan_unroll=scan_unroll)
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step, in_shardings=(
                params_sh, cache_sh, batch_sh, None), donate_argnums=(1,))
            lowered = jitted.lower(state_shape.params, cache_shape,
                                   batch_specs, pos_spec)

        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

        cost = dict(compiled.cost_analysis())
        mem = _memory_dict(compiled)
        hlo = compiled.as_text()
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
    return cost, mem, hlo, (t_lower, t_compile)


def _with_periods(cfg: ArchConfig, n_periods: int) -> ArchConfig:
    import dataclasses
    prefix, _, period = cfg.layer_pattern()
    return dataclasses.replace(
        cfg, num_layers=len(prefix) + n_periods * len(period))


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             rules_extra: Optional[Dict] = None,
             accum_steps: int = 1,
             save_hlo: Optional[str] = None,
             cost_extrapolate: bool = True,
             cfg_overrides: Optional[Dict] = None,
             remat: bool = True,
             tag: str = "") -> Dict:
    """One dry-run cell, two passes:

    A. full depth, **rolled** scan — the compile-success + memory proof
       (this is the production program; fast to partition even at 61 layers)
    B. reduced-depth **unrolled** lowers at two period counts n1 < n2 —
       XLA's cost analysis counts a while body once, so per-period FLOPs /
       bytes / collective bytes come from the exact linear fit
       C(n) = C(n1) + (n - n1)·(C(n2) - C(n1))/(n2 - n1), evaluated at the
       full depth. Costs are exactly linear in identical periods, so this
       is lossless; validated against a full unroll in the tests.
    """
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    spec = SHAPES[shape]
    rules = get_rules(arch)
    if rules_extra:
        rules.update(rules_extra)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size

    if spec.global_batch % _mesh_batch_div(mesh) != 0:
        # batch-1 long-context: the DP axes can't shard batch — spread the
        # KV cache sequence over the whole mesh instead (split-KV decode)
        rules["batch"] = None
        rules["kv_seq"] = ("data", "model")

    # ---- pass A: full model, rolled (memory + compile success)
    cost_a, mem, hlo_a, (t_lower, t_compile) = _lower_compile(
        cfg, spec, mesh, rules, accum_steps=accum_steps, scan_unroll=1,
        save_hlo=save_hlo, remat=remat)

    prefix, n_full, period = cfg.layer_pattern()
    cost = dict(cost_a)
    coll_by_kind = dict(roofline.collective_bytes(hlo_a).bytes_by_kind)
    extrapolated = False
    if cost_extrapolate and n_full > 2:
        n2 = max(2, min(4, 16 // max(len(period), 1)))
        n1 = max(1, n2 // 2)
        if n2 > n1 and n_full > n2:
            c1, _, h1, _ = _lower_compile(_with_periods(cfg, n1), spec, mesh,
                                          rules, accum_steps=accum_steps,
                                          scan_unroll=True, remat=remat)
            c2, _, h2, _ = _lower_compile(_with_periods(cfg, n2), spec, mesh,
                                          rules, accum_steps=accum_steps,
                                          scan_unroll=True, remat=remat)
            for k in ("flops", "bytes accessed"):
                per = (c2.get(k, 0.0) - c1.get(k, 0.0)) / (n2 - n1)
                cost[k] = c1.get(k, 0.0) + (n_full - n1) * per
            b1 = roofline.collective_bytes(h1).bytes_by_kind
            b2 = roofline.collective_bytes(h2).bytes_by_kind
            coll_by_kind = {}
            for k in b1:
                per = (b2[k] - b1[k]) / (n2 - n1)
                coll_by_kind[k] = max(0.0, b1[k] + (n_full - n1) * per)
            extrapolated = True

    mf = roofline.model_flops(cfg, spec, spec.kind)
    coll_total = sum(coll_by_kind.values())
    rep = roofline.RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=float(coll_total),
        t_compute=float(cost.get("flops", 0.0)) / roofline.PEAK_FLOPS,
        t_memory=float(cost.get("bytes accessed", 0.0)) / roofline.HBM_BW,
        t_collective=float(coll_total) / roofline.ICI_BW,
        model_flops_global=mf,
        peak_bytes_per_device=mem.get("peak_bytes"),
        collectives={k: int(v) for k, v in coll_by_kind.items() if v},
    )

    row = rep.row()
    row.update({
        "kind": spec.kind,
        "memory": mem,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hlo_bytes": len(hlo_a),
        "cost_extrapolated": extrapolated,
        "params_total": cfg.params_count(),
        "params_active": cfg.active_params_count(),
        "tag": tag,
    })
    return row


def _print_row(row: Dict):
    mem_gb = (row["memory"].get("peak_bytes") or 0) / 2**30
    print(f"{row['arch']:>18s} {row['shape']:>11s} mesh={row['mesh']:>8s} "
          f"T_comp={row['t_compute_s']:.4f}s T_mem={row['t_memory_s']:.4f}s "
          f"T_coll={row['t_collective_s']:.4f}s dom={row['dominant']:<10s} "
          f"useful={row['useful_fraction']:.2f} "
          f"roofline={row['roofline_fraction']:.3f} peak={mem_gb:.1f}GiB "
          f"(compile {row['compile_s']:.0f}s)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="JSONL output path")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--rules", default=None,
                    help="JSON dict of logical-rule overrides")
    ap.add_argument("--cfg", default=None,
                    help="JSON dict of ArchConfig field overrides")
    ap.add_argument("--accum", dest="accum_steps2", type=int, default=None)
    ap.add_argument("--tag", default="", help="label recorded in the row")
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()

    if args.all:
        ok = fail = 0
        for arch, shape in cells():
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if args.multi_pod:
                cmd.append("--multi-pod")
            if args.no_extrapolate:
                cmd.append("--no-extrapolate")
            if args.out:
                cmd += ["--out", args.out]
            try:
                r = subprocess.run(cmd, timeout=args.timeout)
                rc = r.returncode
            except subprocess.TimeoutExpired:
                rc = -1
                print(f"TIMEOUT {arch} {shape}", flush=True)
            ok += rc == 0
            fail += rc != 0
        print(f"dry-run complete: {ok} ok, {fail} failed")
        sys.exit(1 if fail else 0)

    rules_extra = json.loads(args.rules) if args.rules else None
    if rules_extra:
        rules_extra = {k: tuple(v) if isinstance(v, list) else v
                       for k, v in rules_extra.items()}
    cfg_overrides = json.loads(args.cfg) if args.cfg else None
    row = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   accum_steps=args.accum_steps, save_hlo=args.save_hlo,
                   cost_extrapolate=not args.no_extrapolate,
                   rules_extra=rules_extra, cfg_overrides=cfg_overrides,
                   remat=not args.no_remat, tag=args.tag)
    _print_row(row)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
