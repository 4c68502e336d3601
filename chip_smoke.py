#!/usr/bin/env python3
"""On-chip smoke: drive training and serving once on one TPU chip at
smollm-135m's published width, through the entry points a user calls.

    python chip_smoke.py               # one chip: device, train, serve
    python chip_smoke.py --four-chips  # granite-8b (4 layers) on a (1, 4)
                                       # mesh vs. the same model on chip 0

Phases (one process; nothing here starts a child):

1. device   — a TPU is attached, the kernel backend resolves to compiled
              Pallas (no reference fallback, no interpreter), and the
              compiled train and decode steps contain Mosaic kernels.
2. train    — smollm-135m, LNS-8 + Madam, 3 supervised steps at batch 8 x
              seq 256 with numerics telemetry on; step-1 loss against the
              reference backend, and one Madam update kernel vs. oracle.
3. serve    — packed 8-bit weights, 8 requests over 4 slots on the dense
              engine and on the paged engine (ondemand, then reserve);
              completion, admission and recompile checks, reserve ==
              ondemand streams, prefill logits Pallas vs. reference.

Random weights from ``--seed``; no checkpoint, no network. Every check
that fails exits nonzero. The last stdout line is the JSON verdict, printed
only when every phase passed. This is a bring-up smoke, not a benchmark:
its times include a cold chip and are not comparable across runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

SLOTS, REQUESTS, GEN = 4, 8, 32
# prompt lengths are prefill bucket sizes: a shorter prompt pads its
# prefill tensor, the pad rows attend to whatever their block-table
# entries name (the null page under ondemand, reserved pages under
# reserve), and activations are scaled per tensor, pad rows included — so
# a padded prompt makes the two page policies round differently
PROMPT_LENS = (64, 128)
SAMPLED_RIDS = (3, 6)           # seeded temperature sampling; rest greedy
PAGE = 16
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 256
# one Madam update, kernel vs. oracle on identical inputs: the two share
# _step_math, so only a last-ulp difference in rsqrt between Mosaic and XLA
# can move a word, and only when the target exponent sits within that ulp
# of a rounding boundary. A layout or sign-bit fault moves far more.
MADAM_MISMATCH_BOUND = 1e-3
# step-1 loss, Pallas vs. reference backend: both decode the same packed
# words, but Mosaic and XLA may round log2/exp2 differently by an ulp, which
# moves an activation by one LNS-8 code (2^(1/8), ~9%) at a rounding
# boundary. Averaged over 2048 tokens that stays far below 1% of the loss.
LOSS_REL_BOUND = 1e-2
# prefill logits, Pallas vs. reference, relative to the reference's largest
# logit: the same one-code flips, compounded through 30 LNS-8 layers. Paths
# that differ only in rounding differ by about 6% of the largest logit on
# this model and prompt (on a CPU: dense vs. paged attention on the
# reference backend, and interpreted Pallas vs. reference), so the bound
# leaves 4x room; a kernel fault (a wrong page, a dropped mask)
# decorrelates the logits, an O(1) difference.
LOGITS_REL_BOUND = 0.25


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {body}", flush=True)


def device_phase():
    import jax

    from repro.kernels import dispatch

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU attached: jax.devices()[0].platform="
          f"{devs[0].platform!r}")
    backend, interpret = dispatch.resolve_backend(), \
        dispatch.resolve_interpret()
    check(backend == "pallas",
          f"kernel backend resolves to {backend!r}, not 'pallas' "
          f"(is {dispatch.ENV_BACKEND} set?)")
    check(interpret is False,
          f"Pallas resolves to interpret mode "
          f"(is {dispatch.ENV_INTERPRET} set?)")
    say("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), backend=backend, interpret=interpret)
    return devs


def custom_calls(hlo: str) -> int:
    """Mosaic kernels in a compiled program's text: > 0 proves the Pallas
    kernels neither fell back to the reference nor ran interpreted."""
    return hlo.count("tpu_custom_call")


# ---------------------------------------------------------------------------
# train


def train_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, get_rules
    from repro.core.lns import LNSWeight
    from repro.core.quantizer import QuantConfig
    from repro.distributed.params_sharding import batch_shardings
    from repro.distributed.sharding import shard_ctx
    from repro.kernels import dispatch
    from repro.launch.mesh import make_host_mesh
    from repro.obs import NumericsObserver
    from repro.optim.madam import MadamConfig
    from repro.training import build_train_step, init_train_state
    from repro.training.data import SyntheticLM
    from repro.training.loop import SupervisorConfig, run_supervised

    arch = "smollm-135m"
    cfg, rules = get_config(arch), get_rules(arch)
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(lr=2.0 ** -7)
    mesh = make_host_mesh(1, 1)
    with shard_ctx(mesh, rules):
        state = init_train_state(jax.random.PRNGKey(seed), cfg, mcfg)
        n = sum(x.size for x in jax.tree.leaves(state.params))
        data = SyntheticLM(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed)

        def put(b):
            b = jax.tree.map(jnp.asarray, b)
            return jax.device_put(b, batch_shardings(b, mesh, rules))

        batch0 = put(data.batch_at(0))
        t0 = time.perf_counter()
        step = jax.jit(build_train_step(cfg, qcfg, mcfg, numerics=True)) \
            .lower(state, batch0).compile()
        compile_s = time.perf_counter() - t0
        n_kernels = custom_calls(step.as_text())
        say("train", arch=cfg.name, params=n, layers=cfg.num_layers,
            d_model=cfg.d_model, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
            compile_s=f"{compile_s:.2f}", tpu_custom_call=n_kernels)
        check(n_kernels > 0, "compiled train step holds no Mosaic kernel")

        step_s = []

        def timed_step(st, b):
            t = time.perf_counter()
            out = jax.block_until_ready(step(st, b))
            step_s.append(time.perf_counter() - t)
            return out

        with tempfile.TemporaryDirectory() as ckpt_dir:
            report = run_supervised(
                timed_step, state, data, CheckpointManager(ckpt_dir, keep=1),
                SupervisorConfig(max_steps=TRAIN_STEPS,
                                 save_every=TRAIN_STEPS + 1),
                device_put_batch=put, observer=NumericsObserver(quiet=True))
        peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        say("train", steps_done=report.steps_done,
            failures_recovered=report.failures_recovered,
            losses=[round(x, 6) for x in report.losses],
            step_s=[f"{s:.4f}" for s in step_s], peak_bytes_in_use=peak)
        check(report.steps_done == TRAIN_STEPS,
              f"{report.steps_done}/{TRAIN_STEPS} train steps done")
        check(report.failures_recovered == 0,
              f"{report.failures_recovered} train step failures recovered")
        check(all(math.isfinite(x) for x in report.losses),
              f"non-finite train loss: {report.losses}")

        with dispatch.configured(backend="reference"):
            ref_step = jax.jit(build_train_step(cfg, qcfg, mcfg,
                                                numerics=True))
            _, ref_metrics = ref_step(state, batch0)
        ref_loss = float(ref_metrics["loss"])
        rel = abs(report.losses[0] - ref_loss) / abs(ref_loss)
        say("train", step1_loss_pallas=f"{report.losses[0]:.6f}",
            step1_loss_reference=f"{ref_loss:.6f}", rel_diff=f"{rel:.3e}",
            bound=LOSS_REL_BOUND)
        check(rel <= LOSS_REL_BOUND,
              f"step-1 loss differs by {rel:.3e} between backends")

    # one Madam update on every packed leaf, kernel vs. oracle, identical
    # (word, grad, v) inputs
    leaves = [w for w in jax.tree.leaves(
        state.params, is_leaf=lambda x: isinstance(x, LNSWeight))
        if isinstance(w, LNSWeight)]
    rng = np.random.default_rng(seed)
    count = jnp.ones((), jnp.int32)
    diff = total = 0
    for w in leaves:
        g = jnp.asarray(rng.standard_normal(w.packed.shape), jnp.float32)
        v = jnp.zeros(w.packed.shape, jnp.float32)
        out = {}
        for backend in ("pallas", "reference"):
            with dispatch.configured(backend=backend):
                fn = jax.jit(lambda p, g, v, c, fmt=w.fmt: dispatch.madam_step(
                    p, g, v, c, fmt, lr=mcfg.lr, beta=mcfg.beta)[0])
                out[backend] = np.asarray(fn(w.packed, g, v, count))
        diff += int((out["pallas"] != out["reference"]).sum())
        total += out["pallas"].size
    frac = diff / total
    say("train", madam_words=total, madam_mismatch=diff,
        madam_mismatch_frac=f"{frac:.3e}", bound=MADAM_MISMATCH_BOUND)
    check(frac <= MADAM_MISMATCH_BOUND,
          f"Madam kernel vs. oracle: {frac:.3e} of packed words differ")


# ---------------------------------------------------------------------------
# serve


def make_requests(cfg, seed: int):
    import numpy as np

    from repro.server.sampling import SamplingParams
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(REQUESTS):
        plen = int(rng.choice(PROMPT_LENS))
        samp = SamplingParams(temperature=0.8, seed=1000 + rid) \
            if rid in SAMPLED_RIDS else None
        reqs.append(Request(
            rid=rid, prompt=rng.integers(1, cfg.vocab_size, plen).tolist(),
            max_new_tokens=GEN, sampling=samp))
    return reqs


def decode_hlo(engine) -> str:
    """Text of the engine's compiled decode step, lowered on the engine's
    current state with the arguments ``Engine.step`` passes."""
    import jax.numpy as jnp

    batch = {"tokens": engine._put(engine._last_tok[:, None])}
    if engine.page_size:
        batch["block_tables"] = engine._put(engine._block_tables)
    pos = engine._put(engine._slot_len, jnp.int32)
    samp = {k: engine._put(v) for k, v in engine._samp.items()}
    with engine._ctx():
        return engine._decode_fn.lower(engine.params, engine.caches, batch,
                                       pos, samp).compile().as_text()


def first_diffs(a, b):
    """``{rid: index of the first token where two runs' streams part}``,
    ``None`` where they agree throughout."""
    return {r: next((i for i, (x, y) in enumerate(zip(a[r], b[r]))
                     if x != y), None) for r in sorted(a)}


def serve_run(label, cfg, qcfg, mcfg, params, seed, **engine_kw):
    """One offline run of the engine over the smoke trace; checks and
    returns ``({rid: tokens}, engine)``."""
    from repro.serving import Engine

    engine = Engine(cfg, qcfg, mcfg, params, num_slots=SLOTS,
                    max_len=max(PROMPT_LENS) + GEN, **engine_kw)
    t0 = time.perf_counter()
    agg = engine.run(make_requests(cfg, seed))
    wall = time.perf_counter() - t0
    errors = sum(rs.finish_reason == "error" for rs in engine.aborted)
    hlo = decode_hlo(engine)
    n_kernels = custom_calls(hlo)
    say(label, completed=int(agg["completed"]), submitted=REQUESTS,
        errors=errors, admit_failures=engine.admit_failures,
        decode_steps=engine.decode_steps,
        prefill_compiles=engine.prefill_compiles,
        decode_compiles=engine.decode_compiles,
        decode_tpu_custom_call=n_kernels,
        wall_s=f"{wall:.2f}")
    check(int(agg["completed"]) == REQUESTS and not errors,
          f"{label}: {int(agg['completed'])}/{REQUESTS} completed, "
          f"{errors} errors")
    check(engine.admit_failures == 0,
          f"{label}: {engine.admit_failures} admission failures")
    check(engine.decode_compiles == 1,
          f"{label}: decode step compiled {engine.decode_compiles} times")
    check(n_kernels > 0,
          f"{label}: compiled decode step holds no Mosaic kernel")
    streams = {rs.request.rid: list(rs.generated) for rs in engine.finished}
    check(all(len(t) == GEN for t in streams.values()),
          f"{label}: a request stopped short of {GEN} tokens")
    return streams, engine, hlo


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_rules
    from repro.core.lns import LNSFormat
    from repro.core.quantizer import QuantConfig
    from repro.distributed.sharding import shard_ctx
    from repro.kernels import dispatch
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import forward, init_caches
    from repro.optim.madam import MadamConfig
    from repro.training import init_train_state

    arch = "smollm-135m"
    cfg = get_config(arch)
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(update_format=LNSFormat(bits=8, gamma=8))
    with shard_ctx(make_host_mesh(1, 1), get_rules(arch)):
        params = init_train_state(jax.random.PRNGKey(seed), cfg, mcfg).params
        dense, _, _ = serve_run("serve-dense", cfg, qcfg, mcfg, params, seed)
        paged, _, _ = serve_run("serve-paged", cfg, qcfg, mcfg, params, seed,
                                page_size=PAGE, alloc_policy="ondemand")
        reserve, _, _ = serve_run("serve-paged-reserve", cfg, qcfg, mcfg,
                                  params, seed, page_size=PAGE)
        # dense and paged attention round differently (a different
        # attention datapath), and one flipped LNS-8 code compounds through
        # 30 layers, so their streams part at a near-tie: reported, not
        # gated. Where DESIGN.md §7.1 says the schedules coincide — one
        # paged engine under both page policies, an unpressured pool, FIFO
        # admission of one t=0 trace — every stream must agree token for
        # token, greedy and seeded.
        same = [r for r in range(REQUESTS) if reserve[r] == paged[r]]
        say("serve", dense_vs_paged_first_diff=first_diffs(dense, paged),
            reserve_vs_ondemand_identical=f"{len(same)}/{REQUESTS}",
            sample_stream=paged[0][:8])
        check(len(same) == REQUESTS,
              f"paged reserve vs ondemand streams differ for rids "
              f"{sorted(set(range(REQUESTS)) - set(same))}")

        # first prefill through the paged path, Pallas vs. reference
        prompt = jnp.asarray([make_requests(cfg, seed)[0].prompt], jnp.int32)
        pages = -(-(max(PROMPT_LENS) + GEN) // PAGE)
        table = jnp.arange(pages, dtype=jnp.int32)[None]
        logits = {}
        for backend in ("pallas", "reference"):
            with dispatch.configured(backend=backend):
                fn = jax.jit(lambda p, c, t, bt: forward(
                    p, t, cfg, qcfg, caches=c, block_tables=bt)[0])
                caches = init_caches(1, max(PROMPT_LENS) + GEN, cfg,
                                     page_size=PAGE)
                logits[backend] = np.asarray(
                    fn(params, caches, prompt, table), np.float32)
        ref, got = logits["reference"], logits["pallas"]
        diff = float(np.max(np.abs(got - ref)))
        scale = float(np.max(np.abs(ref)))
        say("serve", prefill_tokens=prompt.shape[1],
            prefill_logits_max_abs_diff=f"{diff:.4e}",
            ref_logits_max_abs=f"{scale:.4e}",
            bound=f"{LOGITS_REL_BOUND}*{scale:.4e}",
            rel_rms_diff=f"{np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)):.4e}",
            top1_agree=f"{np.mean(got.argmax(-1) == ref.argmax(-1)):.4f}")
        check(np.isfinite(logits["pallas"]).all(), "non-finite logits")
        check(diff <= LOGITS_REL_BOUND * scale,
              f"prefill logits differ by {diff:.4e} between backends")


# ---------------------------------------------------------------------------
# four chips


def four_chip_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config, get_rules
    from repro.core.lns import LNSFormat
    from repro.core.quantizer import QuantConfig
    from repro.distributed.sharding import shard_ctx
    from repro.launch.mesh import make_host_mesh
    from repro.optim.madam import MadamConfig
    from repro.training import init_train_state

    arch = "granite-8b"
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = dataclasses.replace(get_config(arch), num_layers=4)
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(update_format=LNSFormat(bits=8, gamma=8))
    mesh = make_host_mesh(1, 4)
    with shard_ctx(mesh, get_rules(arch)):
        params = init_train_state(jax.random.PRNGKey(seed), cfg, mcfg).params
    params = jax.device_put(params, jax.devices()[0])
    kw = dict(page_size=PAGE, alloc_policy="ondemand")
    one, _, _ = serve_run("serve-1chip", cfg, qcfg, mcfg, params, seed, **kw)
    with shard_ctx(mesh, get_rules(arch)):
        four, engine, hlo = serve_run("serve-mesh", cfg, qcfg, mcfg, params,
                                      seed, mesh=mesh, **kw)
    gathers = sum("all-gather" in line for line in hlo.splitlines())
    same = [r for r in range(REQUESTS) if one[r] == four[r]]
    say("four-chips", arch=cfg.name, layers=cfg.num_layers,
        mesh_devices=engine._mesh.devices.size,
        mesh_shape=dict(engine._mesh.shape), decode_all_gather=gathers,
        streams_identical=f"{len(same)}/{REQUESTS}",
        first_diff=first_diffs(one, four))
    check(engine._mesh.devices.size == 4, "serving mesh is not 4 devices")
    check(len(same) == REQUESTS,
          f"(1,4) mesh vs chip 0 streams differ for rids "
          f"{sorted(set(range(REQUESTS)) - set(same))}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1,4)-mesh paged serving path and "
                         "its single-chip comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        # this script smokes the checkout it sits in, never another copy
        print(f"FAIL: no repro package beside this script ({SRC})",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache
    from repro.launch.mesh import require_exact_rounding
    require_exact_rounding()
    cache = enable_compile_cache() or Path(os.environ[CACHE_ENV])

    def entries():
        return len(list(cache.iterdir())) if cache.is_dir() else 0

    say("cache", dir=cache, set_by=CACHE_ENV if os.environ.get(CACHE_ENV)
        else "checkout", entries_before=entries())
    t0 = time.perf_counter()
    try:
        devs = device_phase()
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            train_phase(args.seed)
            serve_phase(args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    say("cache", entries_after=entries())
    say("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
