"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode — the kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lns import LNSFormat, compute_scale, lns_encode, lns_pack
from repro.kernels import lns_matmul, lns_qmatmul, madam_step, quantize_pack
from repro.kernels import ref as kref
from repro.kernels.lns_matmul import lns_matmul_pallas
from repro.kernels.lns_qmatmul import lns_qmatmul_pallas
from repro.kernels.lns_quantize import lns_quantize_pallas
from repro.kernels.madam_update import madam_update_pallas

# kernel bodies execute in Python on CPU (interpret mode): correct but slow
pytestmark = pytest.mark.interpret

FMT = LNSFormat(bits=8, gamma=8)


def _packed(key, shape, fmt=FMT):
    x = jax.random.normal(key, shape)
    s = compute_scale(x)
    sign, code = lns_encode(x, fmt, s)
    return lns_pack(sign, code, fmt), x, s


# ---------------------------------------------------------------------------
# bit-exact datapath kernel


@pytest.mark.parametrize("m,k,n", [(128, 16, 128), (128, 32, 256),
                                   (256, 64, 128)])
@pytest.mark.parametrize("gamma", [2, 8])
def test_lns_matmul_bit_exact(key, m, k, n, gamma):
    fmt = LNSFormat(bits=8, gamma=gamma)
    pa, _, _ = _packed(jax.random.fold_in(key, 1), (m, k), fmt)
    pb, _, _ = _packed(jax.random.fold_in(key, 2), (k, n), fmt)
    out = lns_matmul_pallas(pa, pb, fmt, block_k=16)
    ref = kref.lns_matmul_ref(pa, pb, fmt, block_k=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("lut_entries", [1, 2, 4, 8])
def test_lns_matmul_hybrid_bit_exact(key, lut_entries):
    """App.-B Mitchell hybrid at every LUT size (Table 10 sweep)."""
    pa, _, _ = _packed(jax.random.fold_in(key, 1), (128, 32))
    pb, _, _ = _packed(jax.random.fold_in(key, 2), (32, 128))
    out = lns_matmul_pallas(pa, pb, FMT, lut_entries=lut_entries, block_k=16)
    ref = kref.lns_matmul_ref(pa, pb, FMT, lut_entries=lut_entries,
                              block_k=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_lns_matmul_end_to_end_accuracy(key):
    """The integer datapath approximates the fp32 matmul to quantization
    accuracy (both operands on the 8-bit LNS grid)."""
    a = jax.random.normal(jax.random.fold_in(key, 1), (64, 48))
    b = jax.random.normal(jax.random.fold_in(key, 2), (48, 40))
    out = lns_matmul(a, b, FMT)
    exact = jnp.dot(a, b)
    err = float(jnp.max(jnp.abs(out - exact)))
    assert err < 0.12 * float(jnp.max(jnp.abs(exact)))


def test_lns_matmul_saturation():
    """Accumulator clamps at +/-(2^23 - 1) like the 24-bit collector."""
    fmt = LNSFormat(bits=8, gamma=8)
    # all-max-magnitude positive codes: every product is 1.0 = 2^16 in Q7.16
    pa = jnp.zeros((128, 256), jnp.uint8)       # code 0, sign + -> value 1.0
    pb = jnp.zeros((256, 128), jnp.uint8)
    out = lns_matmul_pallas(pa, pb, fmt, block_k=16)
    # unsaturated sum would be 256 * 2^16 = 2^24 > SAT24
    assert int(out[0, 0]) == kref.SAT24
    ref = kref.lns_matmul_ref(pa, pb, fmt, block_k=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# fused dequant -> MXU matmul


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (100, 60, 36)])  # odd shapes exercise padding
def test_lns_qmatmul_vs_ref(key, m, k, n):
    pa, a, sa = _packed(jax.random.fold_in(key, 1), (m, k))
    pb, b, sb = _packed(jax.random.fold_in(key, 2), (k, n))
    out = lns_qmatmul(pa, pb, FMT, sa, sb)
    ref = kref.lns_qmatmul_ref(pa, pb, FMT, compute_dtype=jnp.bfloat16) * sa * sb
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_lns_qmatmul_accuracy_vs_fp32(key):
    pa, a, sa = _packed(jax.random.fold_in(key, 1), (128, 128))
    pb, b, sb = _packed(jax.random.fold_in(key, 2), (128, 128))
    out = lns_qmatmul(pa, pb, FMT, sa, sb)
    exact = jnp.dot(a, b)
    rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.08  # 8-bit LNS quantization + bf16 MXU rounding


# ---------------------------------------------------------------------------
# fused quantize+pack


@pytest.mark.parametrize("r,c", [(256, 256), (512, 300), (100, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_kernel_exact(key, r, c, dtype):
    x = jax.random.normal(key, (r, c)).astype(dtype)
    packed, srow = quantize_pack(x, FMT, scale_axis=0)
    ref = kref.lns_quantize_ref(x, srow, FMT)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(ref))


@given(st.integers(1, 4), st.integers(1, 64))
@settings(max_examples=20, deadline=None)
def test_quantize_kernel_property(seed, cols):
    """Packed output always decodes to within one grid step of the input."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (8, cols)) * 3.0
    packed, srow = quantize_pack(x, FMT, scale_axis=0)
    code = (packed & 0x7F).astype(jnp.float32)
    sign = 1.0 - 2.0 * (packed >> 7).astype(jnp.float32)
    dec = sign * jnp.exp2(-code / FMT.gamma) * srow
    rel = jnp.abs(dec - x) / jnp.maximum(jnp.abs(x), 1e-6)
    grid = 2.0 ** (1.0 / (2 * FMT.gamma)) - 1.0
    floor = srow * 2.0 ** (-FMT.dynamic_range)
    ok = (rel <= grid + 1e-5) | (jnp.abs(x) <= floor)
    assert bool(jnp.all(ok))


# ---------------------------------------------------------------------------
# fused Madam update


@pytest.mark.parametrize("r,c", [(256, 256), (100, 70), (512, 10)])
def test_madam_kernel_exact(key, r, c):
    ufmt = LNSFormat(bits=16, gamma=8 * 256)
    code = jax.random.randint(jax.random.fold_in(key, 1), (r, c), 0,
                              ufmt.max_code, jnp.int32).astype(jnp.int16)
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5,
                                          (r, c)), 1, -1).astype(jnp.int8)
    g = jax.random.normal(jax.random.fold_in(key, 3), (r, c))
    v = jnp.abs(jax.random.normal(jax.random.fold_in(key, 4), (r, c)))
    nc, nv = madam_step(code, sign, g, v, jnp.asarray(7), ufmt, lr=2.0 ** -7)
    rc, rv = kref.madam_update_ref(code, sign, g, v, ufmt, lr=2.0 ** -7,
                                   beta=0.999, count=7)
    np.testing.assert_array_equal(np.asarray(nc), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(nv), np.asarray(rv), rtol=1e-6)


def test_madam_kernel_matches_optimizer(key):
    """The fused kernel reproduces optim.madam's leaf update bit-for-bit."""
    from repro.core.lns import lns_pack
    from repro.optim.madam import LNSWeight, MadamConfig, madam_lns
    mcfg = MadamConfig()
    ufmt = mcfg.update_format
    code = jax.random.randint(key, (64, 32), 0, ufmt.max_code,
                              jnp.int32).astype(jnp.int16)
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                                          (64, 32)), 1, -1).astype(jnp.int8)
    scale = jnp.ones((1, 32))
    params = {"w": LNSWeight(packed=lns_pack(sign, code, ufmt), scale=scale,
                             fmt=ufmt)}
    init, update = madam_lns(mcfg)
    st0 = init(params)
    g = {"w": jax.random.normal(jax.random.fold_in(key, 2), (64, 32))}
    new_p, new_st = update(g, st0, params)
    # kernel path: v starts at 0, count becomes 1
    knc, knv = madam_step(code, sign, g["w"], jnp.zeros((64, 32)),
                          jnp.asarray(1), ufmt, lr=mcfg.lr, beta=mcfg.beta,
                          eps=mcfg.eps)
    np.testing.assert_array_equal(np.asarray(new_p["w"].code), np.asarray(knc))
    np.testing.assert_array_equal(np.asarray(new_p["w"].sign), np.asarray(sign))
    np.testing.assert_allclose(np.asarray(new_st.g2["w"]), np.asarray(knv),
                               rtol=1e-6)


def test_madam_packed_kernel_matches_unpacked(key):
    """Packed-word kernel == unpacked (code, sign) kernel, word for word."""
    from repro.core.lns import lns_pack, lns_unpack
    ufmt = LNSFormat(bits=16, gamma=8 * 256)
    code = jax.random.randint(key, (100, 70), 0, ufmt.max_code,
                              jnp.int32).astype(jnp.int16)
    sign = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                                          (100, 70)), 1, -1).astype(jnp.int8)
    g = jax.random.normal(jax.random.fold_in(key, 2), (100, 70))
    v = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (100, 70)))
    packed = lns_pack(sign, code, ufmt)
    from repro.kernels import madam_step_packed
    npk, nv = madam_step_packed(packed, g, v, jnp.asarray(5), ufmt,
                                lr=2.0 ** -7)
    rc, rv = madam_step(code, sign, g, v, jnp.asarray(5), ufmt, lr=2.0 ** -7)
    s2, c2 = lns_unpack(npk, ufmt)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(sign))
    np.testing.assert_allclose(np.asarray(nv), np.asarray(rv), rtol=1e-6)
