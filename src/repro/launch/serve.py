"""Serving CLI: a thin driver over the continuous-batching engine.

Offline (default): builds a mixed-length synthetic request trace,
initializes the model in the packed 8-bit LNS serving format, and drives
``repro.serving.Engine`` — variable-length requests are admitted into
freed decode slots mid-run, finished sequences release their KV rows, and
per-request TTFT / latency / tokens-per-second are reported alongside the
aggregate goodput.

  python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 8 --slots 4 --prompt-len 32 --gen-len 32

Online (``--http HOST:PORT``): boots the asyncio gateway
(``repro.server``) over the same engine instead of replaying a trace —
OpenAI-style ``POST /v1/completions`` with per-request sampling and SSE
token streaming, ``DELETE /v1/requests/{id}`` mid-flight cancellation,
``GET /health`` / ``GET /metrics``. Ctrl-C shuts down cleanly (live
requests are aborted, their slots and KV pages released).

  python -m repro.launch.serve --arch smollm-135m --smoke \
      --http 127.0.0.1:8000
  curl -N localhost:8000/v1/completions -d \
      '{"prompt": [1,2,3], "max_tokens": 8, "stream": true}'
"""
import argparse
import asyncio

import jax

from repro.configs import get_config, get_rules, get_smoke_config
from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig
from repro.distributed.sharding import shard_ctx
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, require_exact_rounding
from repro.optim.madam import MadamConfig
from repro.serving import Engine, max_trace_len, synthetic_trace
from repro.training import init_train_state


def _serve_http(engine, http: str, model: str, max_queue: int) -> None:
    """Run the online gateway until interrupted; clean shutdown aborts
    live requests so their slots and KV pages are released."""
    from repro.server.app import Gateway
    from repro.server.driver import EngineDriver

    host, _, port = http.rpartition(":")
    driver = EngineDriver(engine, max_inflight=max_queue).start()

    async def _run():
        gw = await Gateway(driver, host=host or "127.0.0.1",
                           port=int(port or 8000), model=model).start()
        h, p = gw.address
        print(f"gateway listening on http://{h}:{p}  "
              f"(slots={engine.num_slots} max_len={engine.max_len} "
              f"max_queue={max_queue})", flush=True)
        try:
            await gw.serve_forever()
        finally:
            await gw.stop()

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        print("shutting down: aborting live requests", flush=True)
    finally:
        driver.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (concurrent sequences)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="vary prompt/gen lengths across the trace "
                         "(alias for --lengths uniform)")
    ap.add_argument("--lengths", default=None,
                    choices=("fixed", "uniform", "bimodal"),
                    help="trace length distribution (bimodal = the "
                         "serving bench's short-chat/long-doc mix)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s (0 = all at t=0)")
    ap.add_argument("--serve-bits", type=int, default=8,
                    help="LNS weight bitwidth for serving")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size in tokens: switch the full-context "
                         "attention layers to the block-paged pool")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool pages per layer (default: dense-equivalent "
                         "slots * ceil(max_len / page_size))")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page reuse")
    ap.add_argument("--alloc-policy", default="reserve",
                    choices=("reserve", "ondemand"),
                    help="paged-KV page claiming: 'reserve' takes the "
                         "worst case up front, 'ondemand' grows the block "
                         "table as decode proceeds and preempts by "
                         "recompute under pool pressure")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="self-speculative decoding: draft tokens per "
                         "fused draft+verify cycle (0 = off)")
    ap.add_argument("--draft-bitwidth", type=int, default=6,
                    help="wire bitwidth of the draft re-grid view "
                         "(8 = identity draft; 6/7 = coarser LNS grid)")
    ap.add_argument("--spec-autotune", action="store_true",
                    help="explore (draft bitwidth, k) arms from "
                         "accept-rate/throughput feedback")
    ap.add_argument("--http", default=None, metavar="HOST:PORT",
                    help="serve online over HTTP/SSE instead of replaying "
                         "a synthetic trace (port 0 = ephemeral)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-slot cache capacity (online mode; offline "
                         "derives it from the trace distribution)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission-control watermark: live requests "
                         "beyond this are refused with 429")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serving mesh shape, e.g. '2,2': the model axis "
                         "head-/column-shards weights, KV pools and the "
                         "paged-attend kernel; default is a (devices, 1) "
                         "mesh (single-device semantics on 1 device)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="enable request span tracing + the step "
                         "timeline; export Chrome trace-event JSON "
                         "(opens in Perfetto / chrome://tracing) into "
                         "DIR when the run ends")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace "
                         "(TensorBoard format) written to DIR: the "
                         "engine's repro.engine.* phase spans beside the "
                         "device ops, each op under its model scope")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    require_exact_rounding()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(
        update_format=LNSFormat(bits=args.serve_bits, gamma=8))
    if args.mesh:
        try:
            data, model = (int(v) for v in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh expects 'DATA,MODEL', got {args.mesh!r}")
        mesh = make_host_mesh(data=data, model=model)
    else:
        mesh = make_host_mesh(data=jax.device_count())

    with shard_ctx(mesh, get_rules(args.arch)):
        state = init_train_state(jax.random.PRNGKey(args.seed), cfg, mcfg)
        bytes_w = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(state.params))
        print(f"arch={cfg.name} serve weights {bytes_w / 2**20:.1f} MiB "
              f"(packed {args.serve_bits}-bit LNS codes + scales)")

        lengths = args.lengths or ("uniform" if args.mixed else "fixed")
        max_len = args.max_len or max_trace_len(args.prompt_len,
                                                args.gen_len, lengths)
        observer = None
        if args.trace_dir:
            from repro.obs import EngineObserver
            observer = EngineObserver()
        engine = Engine(cfg, qcfg, mcfg, state.params,
                        num_slots=args.slots, max_len=max_len,
                        page_size=args.page_size, num_pages=args.num_pages,
                        prefix_cache=not args.no_prefix_cache,
                        alloc_policy=args.alloc_policy,
                        speculate_k=args.speculate_k,
                        draft_bitwidth=args.draft_bitwidth,
                        spec_autotune=args.spec_autotune,
                        mesh=mesh if mesh.devices.size > 1 else None,
                        observer=observer,
                        checkpoint_id=f"{cfg.name}-seed{args.seed}-init")
        if args.http:
            try:
                _serve_http(engine, args.http, cfg.name, args.max_queue)
            finally:
                if observer is not None:
                    print("trace:", observer.export(args.trace_dir,
                                                    tag=cfg.name))
            return
        trace = synthetic_trace(cfg, requests=args.requests,
                                prompt_len=args.prompt_len,
                                gen_len=args.gen_len, lengths=lengths,
                                rate=args.rate, seed=args.seed)
        if args.jax_profile:
            with jax.profiler.trace(args.jax_profile):
                agg = engine.run(trace)
        else:
            agg = engine.run(trace)

        print(f"slots={args.slots} requests={args.requests} "
              f"decode_steps={engine.decode_steps} "
              f"prefill_compiles={engine.prefill_compiles} "
              f"decode_compiles={engine.decode_compiles}")
        if engine.page_size:
            print(f"paged KV: page_size={engine.page_size} "
                  f"pages={engine.num_pages} "
                  f"alloc_policy={engine.alloc_policy} "
                  f"preemptions={engine.preemptions} "
                  f"prefix_hits={engine.prefix_hits} "
                  f"reused_tokens={engine.prefix_reused_tokens}")
        if engine.spec is not None:
            print(f"speculative: cycles={engine.spec_cycles} "
                  f"k={engine._spec_arm[1]} "
                  f"draft_bits={engine._spec_arm[0]} "
                  f"accept_rate={engine.spec_accept_rate:.3f} "
                  f"emitted={engine.spec_emitted} "
                  f"fallbacks={engine.spec_fallbacks} "
                  f"pages_trimmed={engine.spec_pages_trimmed}")
        print(f"completed {int(agg['completed'])} requests in "
              f"{agg['wall_s']:.2f}s: {agg['tokens_per_s']:.1f} tok/s, "
              f"ttft mean {agg['ttft_mean_s']:.3f}s "
              f"p95 {agg['ttft_p95_s']:.3f}s, "
              f"latency p50 {agg['latency_p50_s']:.3f}s "
              f"p95 {agg['latency_p95_s']:.3f}s")
        for rs in sorted(engine.finished, key=lambda r: r.request.rid)[:4]:
            head = rs.generated[:8]
            print(f"  req {rs.request.rid}: prompt {rs.request.prompt_len} "
                  f"-> {len(rs.generated)} new tokens, sample {head}")
        if observer is not None:
            bd = observer.time_breakdown(engine.phase_stats(),
                                         agg["wall_s"])
            print(f"time breakdown: device wait {bd.get('wait_share', 0):.1%} "
                  f"host {bd.get('host_share', 0):.1%} "
                  f"outside steps {bd.get('outside_share', 0):.1%}")
            print("trace:", observer.export(args.trace_dir, tag=cfg.name))
    # the engine isolates a failing admission so that an online gateway
    # keeps serving; an offline run that lost requests has failed
    errors = sum(rs.finish_reason == "error" for rs in engine.aborted)
    if int(agg["completed"]) != args.requests or errors:
        raise SystemExit(
            f"serving failed: {int(agg['completed'])}/{args.requests} "
            f"requests completed, {errors} ended in error")


if __name__ == "__main__":
    main()
