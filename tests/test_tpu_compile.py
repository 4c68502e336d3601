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
import re

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


def _attend_grads(q, k, v):
    """Forward plus backward of the dispatched training attention."""
    from repro.kernels import dispatch

    def loss(q, k, v):
        out = dispatch.flash_attend(q, k, v, backend="pallas",
                                    interpret=False)
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def test_flash_attend_fwd_bwd_compiles(one_chip):
    """The train cell's attention core (batch 16 x 2048, 9 heads of 64):
    the forward, dK/dV and dQ kernels all survive as Mosaic calls."""
    qkv = ((16, 2048, CFG.num_heads, CFG.head_dim), jnp.bfloat16)
    hlo = _compile(_attend_grads, qkv, qkv, qkv, sharding=one_chip)
    assert hlo.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("heads,kernel", [(8, True), (9, False)],
                         ids=["heads-sharded", "sequence-parallel"])
def test_flash_attend_on_four_chip_mesh(topo, heads, kernel):
    """Under a (1, 4) mesh the kernel runs per head shard; a head count
    the model axis does not divide keeps the jnp oracle."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import shard_ctx

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    x = jax.ShapeDtypeStruct((2, 512, heads, CFG.head_dim), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    with shard_ctx(mesh):
        hlo = jax.jit(_attend_grads).lower(x, x, x).compile().as_text()
    assert ("tpu_custom_call" in hlo) is kernel


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


# the model's and the step builders' top-level named_scopes; "core" and
# "kv_write" nest inside "attention"
TOP_SCOPES = ("embed", "attention", "mlp", "head", "grad_quantize",
              "optimizer", "sample")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]*\((.*)\)$")


def _scope_path(op_name):
    """``op_name``'s components with transform wrappers removed:
    ``transpose(jvp(attention))`` -> ``attention``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def _instructions(hlo_text):
    """``(opcode, op_name, is_mosaic_call)`` of every compiled
    instruction."""
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            name = _OP_NAME.search(line)
            yield (m.group(1), name.group(1) if name else "",
                   'custom_call_target="tpu_custom_call"' in line)


def _placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("step", ["train", "decode"])
def test_step_ops_sit_under_one_top_level_scope(one_chip,
                                                 smoke_serving_setup, step):
    """The compiled train step and the engine's decode step at the smoke
    config: every matmul (``dot``/``convolution``, fused or not) and every
    Mosaic kernel call carries exactly one of the model's top-level
    ``named_scope``s in its ``op_name``, transforms (``jvp``,
    ``transpose``) unwrapped."""
    from repro.kernels import dispatch
    from repro.serving import Engine
    from repro.training.steps import build_train_step, init_train_state

    cfg, qcfg, mcfg, params = smoke_serving_setup
    if step == "train":
        state = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, mcfg))
        batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        fn, args = jax.jit(build_train_step(cfg, qcfg, mcfg)), (state, batch)
        want = {"embed", "attention", "attention/core", "mlp", "head",
                "grad_quantize", "optimizer"}
    else:
        eng = Engine(cfg, qcfg, mcfg, params, num_slots=4, max_len=64,
                     page_size=16)
        batch = {"tokens": eng._last_tok[:, None],
                 "block_tables": eng._block_tables}
        fn, args = eng._decode_fn, (eng.params, eng.caches, batch,
                                    eng._slot_len.astype("int32"),
                                    dict(eng._samp))
        want = {"embed", "attention", "attention/core", "attention/kv_write",
                "mlp", "head", "sample"}
    with dispatch.configured(backend="pallas", interpret=False):
        text = fn.lower(*_placed(args, one_chip)).compile().as_text()
    seen, kernels = set(), 0
    for opcode, op_name, custom in _instructions(text):
        # XLA joins the op names of merged ops with ";"
        paths = [_scope_path(op) for op in op_name.split(";")]
        tops = [[p for p in path if p in TOP_SCOPES] for path in paths]
        assert all(len(t) <= 1 for t in tops), op_name
        if opcode in ("dot", "convolution") or custom:
            assert len({s for t in tops for s in t}) == 1, op_name
            kernels += custom
        for path, top in zip(paths, tops):
            if top:
                sub = [p for p in ("core", "kv_write")
                       if top == ["attention"] and p in path]
                seen.add("/".join(top + sub[:1]))
                break
    assert kernels > 0
    assert want <= seen
