"""Every main-path Pallas kernel compiles for TPU v5e at smollm-135m widths.

Nothing runs: each test lowers a kernel through ``kernels/ops.py`` with
``interpret=False`` against a described (not attached) ``v5e:2x2``
topology and compiles it with the TPU compiler, which refuses what Mosaic
would refuse on the chip — unaligned block shapes, unsupported casts,
over-budget VMEM. A compiled program that still holds a
``tpu_custom_call`` proves the kernel survived as a Mosaic kernel.

The topology is described inside a module fixture (never at import), so
each pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.lns import LNSFormat
from repro.kernels import ops

CFG = get_config("smollm-135m")
F8 = LNSFormat(bits=8, gamma=8)
PAGE, PAGES, SLOTS = 16, 64, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise write its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip is written to the
        # persistent cache but cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [SLOTS, 512], ids=["decode", "prefill"])
def test_lns_qmatmul_compiles(one_chip, m):
    hlo = _compile(lambda a, b: ops.lns_qmatmul(a, b, F8, interpret=False),
                   ((m, CFG.d_model), jnp.uint8),
                   ((CFG.d_model, CFG.d_ff), jnp.uint8), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_quantize_pack_compiles(one_chip):
    hlo = _compile(lambda x: ops.quantize_pack(x, F8, None, interpret=False),
                   ((512, CFG.d_model), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_requant_pack_compiles(one_chip):
    draft = LNSFormat(bits=6, gamma=2)
    hlo = _compile(lambda p: ops.requant_pack(p, F8, draft, interpret=False),
                   ((CFG.d_model, CFG.d_ff), jnp.uint8), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "stats"])
def test_madam_step_packed_compiles(one_chip, stats):
    step = ops.madam_step_packed_stats if stats else ops.madam_step_packed
    w = (CFG.d_model, CFG.d_ff)
    hlo = _compile(lambda p, g, v, c: step(p, g, v, c, F8, lr=2.0 ** -7,
                                           interpret=False),
                   (w, jnp.uint8), (w, jnp.float32), (w, jnp.float32),
                   ((), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "gumbel"])
def test_fused_sample_compiles(one_chip, sampled):
    row = ((SLOTS, CFG.vocab_size), jnp.float32)
    if sampled:
        hlo = _compile(lambda lg, gm, t: ops.fused_sample(lg, gm, t,
                                                          interpret=False),
                       row, row, ((SLOTS,), jnp.float32), sharding=one_chip)
    else:
        hlo = _compile(lambda lg: ops.fused_sample(lg, None, None,
                                                   interpret=False),
                       row, sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("seq", [1, 16], ids=["decode", "prefill"])
def test_paged_attend_lns_pool_compiles(one_chip, seq):
    batch = SLOTS if seq == 1 else 1
    kv, hd = CFG.num_kv_heads, CFG.head_dim
    pool = (PAGES + 1, kv, PAGE, hd)
    scale = (PAGES + 1, kv, PAGE, 1)
    hlo = _compile(
        lambda q, kp, vp, ks, vs, bt, ln: ops.paged_attend_blocktable(
            q, kp, vp, ks, vs, bt, ln, fmt=F8, sm_scale=hd ** -0.5,
            interpret=False),
        ((batch, seq, CFG.num_heads, hd), jnp.bfloat16),
        (pool, jnp.uint8), (pool, jnp.uint8),
        (scale, jnp.bfloat16), (scale, jnp.bfloat16),
        ((batch, PAGES // batch if seq == 1 else PAGES), jnp.int32),
        ((batch,), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_dispatched_kernels_compile_on_four_chip_mesh(topo):
    """XLA cannot partition a Mosaic kernel: under a (1, 4) mesh the
    dispatch layer must hand each kernel to shard_map (the routed GEMM
    column-parallel, the sampler replicated)."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import shard_ctx
    from repro.kernels import dispatch

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    repl, cols = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "model"))

    def step(x, pw, ws):
        px, sx = dispatch.encode_pack(x, F8, backend="pallas",
                                      interpret=False)
        y = dispatch.qmatmul(px, pw, F8, sx, ws, compute_dtype=jnp.float32,
                             backend="pallas", interpret=False)
        return dispatch.fused_sample(y, None, None, backend="pallas",
                                     interpret=False)

    args = [jax.ShapeDtypeStruct((SLOTS, CFG.d_model), jnp.float32,
                                 sharding=repl),
            jax.ShapeDtypeStruct((CFG.d_model, CFG.d_ff), jnp.uint8,
                                 sharding=cols),
            jax.ShapeDtypeStruct((1, CFG.d_ff), jnp.float32, sharding=cols)]
    with shard_ctx(mesh):
        hlo = jax.jit(step).lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3
