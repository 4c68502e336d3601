"""Mesh-native serving: the (data=2, model=2) host mesh must be
token-for-token equal to the single-device engine.

These tests need >= 4 host devices; the CI ``mesh-smoke`` leg provides
them with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (set
before jax imports — pytest collection of this file skips cleanly on a
single device).

The equality matrix covers the acceptance criteria: smollm smoke over
dense and paged-ondemand KV, greedy and seeded sampling in one trace,
speculation on and off. The MoE smoke (deepseek: 8 experts sharded 2-way,
MLA dense cache) asserts admit + completion, and the paged tests assert
page-pool refcounts return to baseline after abort/rollback.
"""
import copy

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig
from repro.launch.mesh import make_host_mesh
from repro.optim.madam import MadamConfig
from repro.server.sampling import SamplingParams
from repro.serving import Engine
from repro.serving.request import Request
from repro.training import init_train_state

requires_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices: XLA_FLAGS=--xla_force_host_platform_device_count=4")

MAX_LEN = 32


def _setup(arch: str, seed: int = 0):
    cfg = get_smoke_config(arch)
    qcfg = QuantConfig.lns_madam()
    mcfg = MadamConfig(update_format=LNSFormat(bits=8, gamma=8))
    state = init_train_state(jax.random.PRNGKey(seed), cfg, mcfg)
    return cfg, qcfg, mcfg, state.params


def _trace(cfg, n: int = 6, seed: int = 0):
    """Mixed-length trace, greedy and seeded-sampling rows interleaved."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 10))
        prompt = rng.integers(1, cfg.vocab_size, size=(plen,)).tolist()
        samp = SamplingParams(temperature=0.7, top_k=40,
                              seed=1000 + i) if i % 2 else None
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 10)),
                            sampling=samp))
    return reqs


def _tokens(engine):
    return {rs.request.rid: list(rs.generated)
            for rs in engine.finished + engine.aborted}


def _engines(arch, *, mesh_shape=(2, 2), **kw):
    cfg, qcfg, mcfg, params = _setup(arch)
    base = Engine(cfg, qcfg, mcfg, params, max_len=MAX_LEN, **kw)
    mesh = make_host_mesh(data=mesh_shape[0], model=mesh_shape[1])
    sharded = Engine(cfg, qcfg, mcfg, params, max_len=MAX_LEN, mesh=mesh,
                     **kw)
    return cfg, base, sharded


@requires_mesh
@pytest.mark.parametrize("layout", ["dense", "paged_ondemand"])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_mesh_matches_single_device_tokens(layout, spec_k):
    kw = dict(num_slots=3, speculate_k=spec_k)
    if layout == "paged_ondemand":
        kw.update(page_size=8, alloc_policy="ondemand", num_pages=10)
    cfg, base, sharded = _engines("smollm-135m", **kw)
    # the smollm smoke (3 heads / 1 kv head) cannot head-shard model=2:
    # its equality run exercises the column-parallel mlp + all-gather
    # epilogue and the fully-replicated attention path
    base.run(_trace(cfg))
    sharded.run(_trace(cfg))
    got, want = _tokens(sharded), _tokens(base)
    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], (
            f"{layout} spec_k={spec_k} rid={rid}: mesh stream diverged")
    if layout == "paged_ondemand":
        # refcounts back to baseline: every page free or cached (ref == 0)
        assert sharded.allocator.available == sharded.num_pages


@requires_mesh
@pytest.mark.interpret
def test_mesh_pallas_kernels_match_single_device_tokens():
    """The Pallas backend under a (1, 4) mesh: every dispatched kernel runs
    inside shard_map (Mosaic cannot be auto-partitioned) — GEMMs
    column-parallel, paged attention head-sharded (gemma3's 2 kv heads
    shard model=2 on a (2, 2) mesh), the rest replicated — and the streams
    still equal the single-device Pallas engine's."""
    from repro.kernels import dispatch

    kw = dict(num_slots=2, page_size=8, alloc_policy="ondemand",
              num_pages=12)
    with dispatch.configured(backend="pallas", interpret=True):
        for arch, shape in (("smollm-135m", (1, 4)), ("gemma3-12b", (2, 2))):
            cfg, base, sharded = _engines(arch, mesh_shape=shape, **kw)
            base.run(_trace(cfg, n=4))
            sharded.run(_trace(cfg, n=4))
            assert _tokens(sharded) == _tokens(base), arch


@requires_mesh
def test_mesh_weights_actually_sharded():
    """Guard against a vacuous pass: the (2,2) mesh engine must hold its
    mlp weights column-parallel over the model axis (d_ff divides)."""
    _, _, sharded = _engines("smollm-135m", num_slots=2)
    up = sharded.params["period"]["pos0"]["mlp"]["up"]
    assert "model" in tuple(up.packed.sharding.spec)
    # the paired second GEMM keeps its contraction dim replicated
    down = sharded.params["period"]["pos0"]["mlp"]["down"]
    assert down.packed.sharding.spec[0] is None


@requires_mesh
def test_mesh_abort_returns_pages_to_baseline():
    cfg, base, sharded = _engines("smollm-135m", num_slots=3, page_size=8,
                                  alloc_policy="ondemand", num_pages=10)
    del base
    reqs = _trace(cfg, n=4)
    for r in reqs:
        sharded.submit(copy.copy(r))
    # admit + decode a little, then cancel one running and one queued rid
    for _ in range(3):
        sharded.step()
    running = [rs.request.rid for rs in sharded.scheduler.running.values()]
    assert running, "nothing admitted — test harness is broken"
    sharded.abort(running[0])
    sharded.run(())
    assert sharded.allocator.available == sharded.num_pages


@requires_mesh
def test_mesh_head_sharded_engine_matches_single_device():
    """gemma3 smoke (4 heads / 2 kv heads) head-shards over model=2: the
    paged global layers drive the shard_map paged-attend path, the local
    ring layers the head-sharded dense cache — streams must still match."""
    cfg, base, sharded = _engines("gemma3-12b", num_slots=2, page_size=8,
                                  num_pages=12)
    base.run(_trace(cfg, n=4))
    sharded.run(_trace(cfg, n=4))
    assert _tokens(sharded) == _tokens(base)


@requires_mesh
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attend_shard_map_bitwise(quantized):
    """dispatch.paged_attend under a mesh whose model axis divides the KV
    heads: the per-shard head-group path + all-gather epilogue must be
    *bitwise* the no-mesh result (each shard computes exactly the heads a
    single device would, collectives only concatenate)."""
    from repro.distributed.sharding import serving_rules, shard_ctx
    from repro.kernels import dispatch

    B, S, h, kv, hd = 2, 1, 8, 4, 16
    pages, page = 6, 8
    rng = np.random.default_rng(0)
    q = jax.numpy.asarray(rng.standard_normal((B, S, h, hd)), jax.numpy.float32)
    if quantized:
        kp = jax.numpy.asarray(
            rng.integers(0, 255, (pages + 1, kv, page, hd)), jax.numpy.uint8)
        vp = jax.numpy.asarray(
            rng.integers(0, 255, (pages + 1, kv, page, hd)), jax.numpy.uint8)
        ks = jax.numpy.ones((pages + 1, kv, page, 1), jax.numpy.bfloat16)
        vs = jax.numpy.ones((pages + 1, kv, page, 1), jax.numpy.bfloat16)
        fmt = LNSFormat(bits=8, gamma=8)
    else:
        kp = jax.numpy.asarray(
            rng.standard_normal((pages + 1, kv, page, hd)), jax.numpy.float32)
        vp = jax.numpy.asarray(
            rng.standard_normal((pages + 1, kv, page, hd)), jax.numpy.float32)
        ks = vs = None
        fmt = None
    bt = jax.numpy.asarray([[0, 2, pages], [1, 3, pages]], jax.numpy.int32)
    lengths = jax.numpy.asarray([9, 13], jax.numpy.int32)

    kw = dict(fmt=fmt, softcap=None, sm_scale=hd ** -0.5)
    want = dispatch.paged_attend(q, kp, vp, ks, vs, bt, lengths, **kw)

    mesh = make_host_mesh(data=2, model=2)

    class _KV:  # serving_rules duck-typed cfg
        num_heads, num_kv_heads, d_ff, num_experts = h, kv, 0, 0

    with shard_ctx(mesh, serving_rules(_KV, mesh)):
        got = jax.jit(lambda *a: dispatch.paged_attend(*a, **kw))(
            q, kp, vp, ks, vs, bt, lengths) if quantized else \
            jax.jit(lambda q, kp, vp, bt, ln: dispatch.paged_attend(
                q, kp, vp, None, None, bt, ln, **kw))(q, kp, vp, bt, lengths)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@requires_mesh
def test_mesh_sharded_moe_admits_and_completes():
    """deepseek smoke: 8 experts shard 2-way (expert-parallel psum is
    allowed here — MoE equality is not part of the contract), MLA keeps
    the dense cache. The mesh engine must admit and finish every request."""
    cfg, qcfg, mcfg, params = _setup("deepseek-v3-671b")
    mesh = make_host_mesh(data=2, model=2)
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=MAX_LEN,
                 mesh=mesh)
    wup = eng.params["period"]["pos0"]["moe"]["w_up"]
    # ("stack", "experts", "embed", "moe_ff") -> experts carry the model axis
    assert wup.packed.sharding.spec[1] == "model"  # expert-parallel
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4)
            for i in range(3)]
    eng.run(reqs)
    assert len(eng.finished) == 3
    for rs in eng.finished:
        assert len(rs.generated) == 4
