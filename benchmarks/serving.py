"""Serving throughput: continuous batching vs lock-step batching, dense vs
block-paged KV, and prefix-cache reuse.

Replays one mixed-length request trace through the harnesses over the same
packed-LNS weights and decode step:

  lockstep — requests are processed in fixed groups of ``slots``; every
    group decodes until its *longest* request finishes (the old
    ``launch/serve.py`` shape: finished sequences squat on their slot).
  engine   — ``repro.serving.Engine``: a finished sequence frees its slot
    and cache rows immediately and the next request is admitted mid-decode.
  paged    — the engine over a block-paged KV pool holding the *same* KV
    memory as the dense engine but serving **2x the slots**, with the
    ``ondemand`` allocation policy: a request pins only its prompt's
    pages at admission and grows one page per ``page_size`` decoded
    tokens, preempting the youngest request by recompute when the pool
    runs dry — concurrency is bounded by tokens actually resident, not
    worst-case context. A ``reserve``-policy row (worst-case pages up
    front) is recorded alongside to keep the policy gap on the
    trajectory. Peak concurrency is measured from admit/finish intervals.
  prefix   — a shared-prefix trace through the paged engine with and
    without prefix caching: hits map resident pages into the block table
    and prefill only the suffix (fewer prefill tokens, same output).
  mesh     — the ondemand paged engine again, sharded over a
    ``(data=2, model=2)`` host mesh (recorded only when >= 4 devices are
    visible, i.e. the CI ``mesh-smoke`` leg under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``). On host CPU
    the mesh row measures GSPMD partitioning + collective overhead, not a
    speedup — ``mesh_vs_single_tok_ratio`` is trend-tracked so the
    overhead stays on the trajectory; no invariant gates it until a
    multi-chip baseline lands.
  spec     — the ondemand paged engine with self-speculative decoding at
    draft bitwidths 6/7/8 (k=4 draft tokens per fused draft+verify
    cycle): the draft view re-grids the packed LNS weights to a coarser
    exponent grid, verify scores all k tokens in one S=k pass, and the
    accept rate is measured per bitwidth. The headline ``spec_tok_s`` is
    the best bitwidth's throughput; its ratio to the same-group paged
    baseline is the acceptance gate (spec must beat non-speculative).

  obs      — the ondemand paged engine again with the observability
    layer attached (request span ring + step timeline). Its throughput
    against the obs-disabled ``paged`` row from the same interleave
    group is the overhead gate: ``obs_overhead_pct`` must stay near
    zero, proving tracing is close to free, and the observer's
    time breakdown (device wait, host work inside steps, time outside
    them) rides along as an attachment.

All timed paths are run once to warm the jit caches and then timed over
``REPLAYS`` replays, keeping each harness's best. The engine harnesses
replay **interleaved** (round-robin, one replay each per round): host
noise on shared CPU arrives in multi-second windows, so consecutive
replays of one harness can all land in the same slow window and skew a
cross-harness ratio — interleaving gives every harness a shot at every
window and the per-harness best tracks capability, not the host's mood.
Results also land in ``BENCH_serving.json`` at the repo root. ``--full``
adds an offered-load sweep (arrival rate -> goodput).
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, emit_bench, kernel_roofline, record
from repro.configs import get_smoke_config
from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig
from repro.models.model import init_caches
from repro.optim.madam import MadamConfig
from repro.obs import EngineObserver
from repro.serving import Engine, Request, max_trace_len, synthetic_trace
from repro.training import build_decode_step, init_train_state


REPLAYS = 5  # timed replays per harness; the best one is recorded


def _interleaved_best(engines, trace):
    """Replay ``trace`` through every (already warm) engine, round-robin,
    ``REPLAYS`` rounds; return each harness's fastest replay as
    ``{name: (agg, peak_concurrency, preemptions, decode_page_allocs)}``
    (counters captured at that replay, since a later one overwrites the
    engine's own state)."""
    best = {}
    for _ in range(REPLAYS):
        for name, eng in engines.items():
            eng.reset()
            agg = eng.run(trace)
            cur = best.get(name)
            if cur is None or agg["tokens_per_s"] > cur[0]["tokens_per_s"]:
                best[name] = (agg, _peak_concurrency(eng.completed),
                              eng.preemptions, eng.decode_page_allocs)
    return best


def _peak_concurrency(metrics) -> int:
    """Max simultaneously-admitted requests over the run (a finish at time
    t frees the slot before an admit at the same t takes it)."""
    events = []
    for m in metrics:
        events += [(m.t_admit, 1), (m.t_finish, -1)]
    peak = cur = 0
    for _, d in sorted(events):
        cur += d
        peak = max(peak, cur)
    return peak


def shared_prefix_trace(cfg, *, requests: int, prefix_len: int,
                        suffix_len: int, gen_len: int,
                        seed: int = 0) -> List[Request]:
    """Chat-style trace: one common system-prompt prefix, distinct tails."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).tolist()
    out = []
    for i in range(requests):
        suffix = rng.integers(0, cfg.vocab_size, (suffix_len,)).tolist()
        out.append(Request(rid=i, prompt=prefix + suffix,
                           max_new_tokens=gen_len))
    return out


def run_lockstep(cfg, qcfg, mcfg, params, trace: List[Request], *,
                 slots: int, max_len: int, decode=None):
    """Fixed-group serving; returns (useful_new_tokens, wall_seconds).
    Pass a pre-jitted ``decode`` to share compile caches across replays."""
    if decode is None:
        decode = jax.jit(build_decode_step(cfg, qcfg, mcfg))
    useful = 0
    t0 = time.monotonic()
    for g0 in range(0, len(trace), slots):
        group = trace[g0:g0 + slots]
        pmax = max(r.prompt_len for r in group)
        gmax = max(r.max_new_tokens for r in group)
        tokens = np.zeros((slots, pmax), np.int32)
        for j, r in enumerate(group):
            # left-pad shorter prompts so every row's last prompt token
            # lands at pmax-1 (the lock-step script's fixed-shape premise)
            tokens[j, pmax - r.prompt_len:] = np.asarray(r.prompt)
        caches = init_caches(slots, max_len, cfg)
        logits, caches = decode(params, caches,
                                {"tokens": jnp.asarray(tokens)},
                                jnp.zeros((slots,), jnp.int32))
        tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(slots, 1)
        for step in range(1, gmax):
            pos = jnp.full((slots,), pmax + step - 1, jnp.int32)
            logits, caches = decode(params, caches, {"tokens": tok}, pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32).reshape(slots, 1)
        jax.block_until_ready(tok)
        useful += sum(r.max_new_tokens for r in group)
    return useful, time.monotonic() - t0


def run(requests: int = 24, slots: int = 4, prompt_len: int = 16,
        gen_len: int = 24, sweep: bool = False) -> list[str]:
    cfg = get_smoke_config("smollm-135m")
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(update_format=LNSFormat(bits=8, gamma=8))
    params = init_train_state(jax.random.PRNGKey(0), cfg, mcfg).params
    # bimodal lengths: the regime where lock-step groups stall on their
    # longest member while continuous batching keeps slots occupied
    trace = synthetic_trace(cfg, requests=requests, prompt_len=prompt_len,
                            gen_len=gen_len, lengths="bimodal")
    # distribution bound (covers the sweep's re-drawn traces too)
    max_len = max_trace_len(prompt_len, gen_len, "bimodal")

    rows = []
    decode = jax.jit(build_decode_step(cfg, qcfg, mcfg))
    run_lockstep(cfg, qcfg, mcfg, params, trace, slots=slots,
                 max_len=max_len, decode=decode)  # warm-up: compiles
    wall = min(run_lockstep(cfg, qcfg, mcfg, params, trace, slots=slots,
                            max_len=max_len, decode=decode)[1]
               for _ in range(REPLAYS))
    useful = sum(r.max_new_tokens for r in trace)
    tps_lock = useful / wall
    rows.append(csv_row("serving_lockstep", wall * 1e6,
                        f"tok_s={tps_lock:.1f} requests={requests} "
                        f"slots={slots}"))

    # ---- dense engine + the two paged policies, timed interleaved.
    # The paged pool holds the same KV memory as the dense engine with 2x
    # the slots; ondemand allocation is the headline paged row (pages
    # track tokens actually resident), the reserve policy rides along so
    # the trajectory keeps the cost of worst-case reservation visible.
    page = 16
    num_pages = slots * max_len // page  # dense-equivalent KV positions
    engines = {
        "dense": Engine(cfg, qcfg, mcfg, params, num_slots=slots,
                        max_len=max_len),
        "ondemand": Engine(cfg, qcfg, mcfg, params, num_slots=2 * slots,
                           max_len=max_len, page_size=page,
                           num_pages=num_pages, prefix_cache=False,
                           alloc_policy="ondemand"),
        "reserve": Engine(cfg, qcfg, mcfg, params, num_slots=2 * slots,
                          max_len=max_len, page_size=page,
                          num_pages=num_pages, prefix_cache=False,
                          alloc_policy="reserve"),
    }
    # speculative harnesses share the interleave group so spec_tok_s and
    # paged_tok_s are measured under the same host-noise windows; one
    # engine per draft bitwidth keeps the accept-rate-vs-grid trajectory
    # honest (B=8 is the identity draft — accept ~1.0 by construction)
    spec_k = 4
    spec_bits = (6, 7, 8)
    for b in spec_bits:
        engines[f"spec_b{b}"] = Engine(
            cfg, qcfg, mcfg, params, num_slots=2 * slots, max_len=max_len,
            page_size=page, num_pages=num_pages, prefix_cache=False,
            alloc_policy="ondemand", speculate_k=spec_k, draft_bitwidth=b)
    # observability overhead: an ondemand clone with the span ring +
    # step timeline attached, timed in the same interleave group so the
    # obs-vs-paged ratio sees identical host-noise windows
    observer = EngineObserver()
    engines["obs"] = Engine(cfg, qcfg, mcfg, params, num_slots=2 * slots,
                            max_len=max_len, page_size=page,
                            num_pages=num_pages, prefix_cache=False,
                            alloc_policy="ondemand", observer=observer)
    for eng in engines.values():
        eng.run(trace)     # warm-up: compiles every prefill bucket
    best = _interleaved_best(engines, trace)
    engine = engines["dense"]  # the --full sweep reuses this harness
    agg, dense_peak, _, _ = best["dense"]
    tps_eng = agg["tokens_per_s"]
    rows.append(csv_row(
        "serving_engine", agg["wall_s"] * 1e6,
        f"tok_s={tps_eng:.1f} speedup_vs_lockstep={tps_eng / tps_lock:.2f} "
        f"ttft_p95_s={agg['ttft_p95_s']:.3f}"))

    agg_p, paged_peak, preempts, page_allocs = best["ondemand"]
    agg_r = best["reserve"][0]
    rows.append(csv_row(
        "serving_paged", agg_p["wall_s"] * 1e6,
        f"tok_s={agg_p['tokens_per_s']:.1f} slots={2 * slots} "
        f"kv_pages={num_pages} peak_concurrency={paged_peak} "
        f"preemptions={preempts} "
        f"(dense peak {dense_peak} at equal KV memory; reserve policy "
        f"tok_s={agg_r['tokens_per_s']:.1f})"))

    # ---- self-speculative decoding: accept rate per draft bitwidth and
    # the best bitwidth's throughput. The trace is deterministic and the
    # engine resets between replays, so the counters left by the final
    # replay match every replay's — read them off the engines directly.
    spec_stats = {b: (best[f"spec_b{b}"][0], engines[f"spec_b{b}"])
                  for b in spec_bits}
    best_bits = max(spec_bits,
                    key=lambda b: spec_stats[b][0]["tokens_per_s"])
    agg_s, eng_s = spec_stats[best_bits]
    tps_spec = agg_s["tokens_per_s"]
    tps_paged = agg_p["tokens_per_s"]
    accept_by_bits = {b: spec_stats[b][1].spec_accept_rate
                      for b in spec_bits}
    rows.append(csv_row(
        "serving_speculative", agg_s["wall_s"] * 1e6,
        f"tok_s={tps_spec:.1f} vs_paged={tps_spec / tps_paged:.2f} "
        f"k={spec_k} draft_bits={best_bits} "
        f"accept=" + "/".join(f"b{b}={accept_by_bits[b]:.2f}"
                              for b in spec_bits)))

    # ---- observability overhead: tracing must be near-free. The pct is
    # measured against the obs-disabled ondemand row from the same
    # interleave group; negative values just mean host noise favored
    # the obs replica. A clean extra replay (observer cleared first)
    # yields the time breakdown attachment without replay accumulation.
    agg_o = best["obs"][0]
    tps_obs = agg_o["tokens_per_s"]
    obs_overhead_pct = (1.0 - tps_obs / tps_paged) * 100.0
    observer.clear()
    engines["obs"].reset()
    agg_bd = engines["obs"].run(trace)
    time_breakdown = observer.time_breakdown(
        engines["obs"].phase_stats(), agg_bd["wall_s"])
    rows.append(csv_row(
        "serving_obs", agg_o["wall_s"] * 1e6,
        f"tok_s={tps_obs:.1f} overhead_vs_paged={obs_overhead_pct:.2f}% "
        f"spans={len(observer.spans.snapshot())} "
        f"timeline_rows={len(observer.timeline.samples())}"))

    # ---- prefix caching: shared system prompt, suffix-only prefill
    fine = (8, 16, 32, 64, 128, 256)
    ptrace = shared_prefix_trace(cfg, requests=max(8, requests // 3),
                                 prefix_len=3 * page, suffix_len=6,
                                 gen_len=gen_len // 2)
    stats = {}
    for label, pc in (("off", False), ("on", True)):
        e = Engine(cfg, qcfg, mcfg, params, num_slots=slots,
                   max_len=max_len, page_size=page, buckets=fine,
                   prefix_cache=pc)
        e.run(ptrace)
        e.reset()
        agg_x = e.run(ptrace)
        stats[label] = (e.prefill_tokens, e.prefix_hits,
                        e.prefix_reused_tokens, agg_x)
    (pt_off, _, _, agg_off) = stats["off"]
    (pt_on, hits, reused, agg_on) = stats["on"]
    rows.append(csv_row(
        "serving_prefix_cache", agg_on["wall_s"] * 1e6,
        f"prefill_tokens={pt_on} (vs {pt_off} uncached) "
        f"hits={hits} reused_tokens={reused} "
        f"tok_s={agg_on['tokens_per_s']:.1f}"))

    # ---- mesh serving: the same ondemand paged harness over a (2,2)
    # host mesh, only when the platform exposes enough devices
    mesh_recs: list = []
    if jax.device_count() >= 4:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=2, model=2)
        mesh_eng = Engine(cfg, qcfg, mcfg, params, num_slots=2 * slots,
                          max_len=max_len, page_size=page,
                          num_pages=num_pages, prefix_cache=False,
                          alloc_policy="ondemand", mesh=mesh)
        mesh_eng.run(trace)  # warm-up
        agg_m = None
        for _ in range(REPLAYS):
            mesh_eng.reset()
            cand = mesh_eng.run(trace)
            if agg_m is None or cand["tokens_per_s"] > agg_m["tokens_per_s"]:
                agg_m = cand
        tps_mesh = agg_m["tokens_per_s"]
        mesh_recs = [
            record("mesh_tok_s", tps_mesh, unit="tok_s"),
            # host-CPU meshes pay GSPMD overhead with no extra compute:
            # the ratio tracks that overhead, it is not a speedup claim
            record("mesh_vs_single_tok_ratio", tps_mesh / tps_paged,
                   unit="ratio",
                   derived=f"mesh={tps_mesh:.1f} paged={tps_paged:.1f} "
                           f"shape=data2,model2"),
            record("mesh_devices", int(mesh.devices.size), unit="count"),
        ]
        rows.append(csv_row(
            "serving_mesh", agg_m["wall_s"] * 1e6,
            f"tok_s={tps_mesh:.1f} vs_single={tps_mesh / tps_paged:.2f} "
            f"mesh=data2,model2 slots={2 * slots}"))

    # per-decode-token roofline estimate (TPU-class constants): 2N FLOPs
    # against packed 1 B/param weight reads plus the slot's KV page reads
    n_params = cfg.active_params_count()
    kv_layers = cfg.num_layers
    kv_bytes = (kv_layers * (max_len // 2) * cfg.num_kv_heads
                * cfg.head_dim * 2)  # k+v, ~1 B/elem packed, half-full row
    tok_roofline = kernel_roofline(2.0 * n_params, n_params + kv_bytes)

    emit_bench("serving", [
        record("lockstep_tok_s", tps_lock, unit="tok_s"),
        record("engine_tok_s", tps_eng, unit="tok_s"),
        # dense_tok_s is the regression gate's canonical name for the
        # dense-cache engine on this same trace (== engine_tok_s)
        record("dense_tok_s", tps_eng, unit="tok_s", extra=tok_roofline),
        record("engine_speedup_vs_lockstep", tps_eng / tps_lock,
               unit="ratio"),
        record("engine_ttft_p95_s", agg["ttft_p95_s"], unit="s"),
        record("dense_slots", slots, unit="count"),
        record("dense_peak_concurrency", dense_peak, unit="count"),
        record("paged_tok_s", tps_paged, unit="tok_s", extra=tok_roofline),
        # the machine-independent acceptance metric: paged >= dense
        record("paged_vs_dense_tok_ratio", tps_paged / tps_eng,
               unit="ratio",
               derived=f"paged={tps_paged:.1f} dense={tps_eng:.1f}"),
        record("paged_reserve_tok_s", agg_r["tokens_per_s"], unit="tok_s"),
        record("paged_slots", 2 * slots, unit="count"),
        record("paged_kv_pages", num_pages, unit="count"),
        record("paged_page_size", page, unit="count"),
        record("paged_peak_concurrency", paged_peak, unit="count"),
        record("paged_preemptions", preempts, unit="count"),
        record("paged_decode_page_allocs", page_allocs, unit="count"),
        record("spec_tok_s", tps_spec, unit="tok_s", extra=tok_roofline),
        # the machine-independent acceptance metric: speculating must
        # beat the same paged engine decoding one token per launch
        record("spec_vs_paged_tok_ratio", tps_spec / tps_paged,
               unit="ratio",
               derived=f"spec={tps_spec:.1f} paged={tps_paged:.1f} "
                       f"k={spec_k} draft_bits={best_bits}"),
        record("spec_accept_rate_b6", accept_by_bits[6], unit="ratio"),
        record("spec_accept_rate_b7", accept_by_bits[7], unit="ratio"),
        record("spec_accept_rate_b8", accept_by_bits[8], unit="ratio"),
        record("spec_verify_steps", eng_s.spec_verify_steps, unit="count"),
        record("spec_cycles", eng_s.spec_cycles, unit="count"),
        record("spec_fallbacks", eng_s.spec_fallbacks, unit="count"),
        record("spec_k", spec_k, unit="count"),
        record("spec_draft_bits", best_bits, unit="count"),
        record("obs_tok_s", tps_obs, unit="tok_s"),
        # absolute percentage points vs the obs-disabled ondemand row;
        # tracked by check_regression as an absolute bound (the value
        # sits near zero, so relative change is meaningless)
        record("obs_overhead_pct", obs_overhead_pct, unit="pct",
               derived=f"obs={tps_obs:.1f} paged={tps_paged:.1f}",
               extra={"time_breakdown": time_breakdown}),
        record("prefix_prefill_tokens", pt_on, unit="count"),
        record("prefix_prefill_tokens_uncached", pt_off, unit="count"),
        record("prefix_hits", hits, unit="count"),
        record("prefix_reused_tokens", reused, unit="count"),
        record("prefix_tok_s", agg_on["tokens_per_s"], unit="tok_s"),
        record("noprefix_tok_s", agg_off["tokens_per_s"], unit="tok_s"),
        record("requests", requests, unit="count"),
    ] + mesh_recs)

    if sweep:  # offered load -> goodput curve
        for rate in (2.0, 4.0, 8.0, 16.0):
            engine.reset()
            agg = engine.run(synthetic_trace(
                cfg, requests=requests, prompt_len=prompt_len,
                gen_len=gen_len, lengths="bimodal", rate=rate))
            rows.append(csv_row(
                f"serving_load_{rate:g}rps", agg["wall_s"] * 1e6,
                f"tok_s={agg['tokens_per_s']:.1f} "
                f"ttft_p95_s={agg['ttft_p95_s']:.3f} "
                f"latency_p95_s={agg['latency_p95_s']:.3f}"))
    return rows


if __name__ == "__main__":
    for row in run(sweep=True):
        print(row)
