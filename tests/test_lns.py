"""Multi-base LNS format: representation, rounding, packing (paper §2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lns import (LNSFormat, compute_scale, lns_decode, lns_encode,
                            lns_pack, lns_quantize, lns_requant_packed,
                            lns_unpack, pow2_scale, quantization_gap)


# gamma=1 at 8 bits reaches 2^-127 (f32 subnormal edge) — the paper's own
# Table 3 marks that configuration NaN; we test it at 5 bits instead.
@pytest.mark.parametrize("bits,gamma", [(8, 8), (8, 2), (5, 1), (4, 2),
                                        (8, 32), (16, 2048), (12, 128)])
def test_encode_decode_roundtrip_on_grid(bits, gamma):
    """Decoded values re-encode to the same codes (grid is a fixed point)."""
    fmt = LNSFormat(bits=bits, gamma=gamma)
    codes = jnp.arange(fmt.max_code + 1, dtype=jnp.int32).astype(fmt.code_dtype)
    sign = jnp.where(jnp.arange(codes.size) % 2 == 0, 1, -1).astype(jnp.int8)
    scale = jnp.ones(())
    vals = lns_decode(sign, codes, fmt, scale)
    s2, c2 = lns_encode(vals, fmt, scale)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(sign))


def test_dynamic_range_matches_paper():
    """Table 3: B=8 γ=8 -> range (0, 15.875)."""
    fmt = LNSFormat(bits=8, gamma=8)
    assert fmt.max_code == 127
    assert fmt.dynamic_range == pytest.approx(15.875)


def test_with_bits_preserves_range():
    """§6.1.1: widening Q_U keeps the ~(0,15.9) dynamic range (exact up to
    the max_code = 2^(B-1)-1 off-by-one, <1%)."""
    fmt = LNSFormat(bits=8, gamma=8)
    for bits in (10, 12, 16):
        wide = fmt.with_bits(bits)
        assert wide.dynamic_range == pytest.approx(fmt.dynamic_range, rel=0.01)


@given(st.floats(min_value=-100.0, max_value=100.0,
                 allow_nan=False, allow_infinity=False))
@settings(max_examples=200, deadline=None)
def test_quantize_relative_error_bound(x):
    """|Q(x) - x| <= half the local quantization gap (plus clamp floor)."""
    fmt = LNSFormat(bits=8, gamma=8)
    xa = jnp.asarray([x], jnp.float32)
    q = lns_quantize(xa, fmt)
    if abs(x) < 1e-6:
        return  # near zero: clamped to smallest magnitude
    scale = float(pow2_scale(jnp.abs(xa))[0])
    if abs(x) / scale < 2.0 ** (-fmt.dynamic_range):
        return  # below the representable floor -> clamps
    rel = abs(float(q[0]) - x) / abs(x)
    # grid step is a factor 2^(1/γ): worst-case rel err ~ (2^(1/2γ) - 1)
    assert rel <= 2.0 ** (1.0 / (2 * fmt.gamma)) - 1.0 + 1e-6


def test_sign_preserved_and_monotone(key):
    fmt = LNSFormat(bits=8, gamma=8)
    x = jnp.sort(jnp.abs(jax.random.normal(key, (64,)))) + 0.01
    q = lns_quantize(x, fmt)
    assert bool(jnp.all(q > 0))
    assert bool(jnp.all(jnp.diff(q) >= 0))  # monotone non-decreasing
    qn = lns_quantize(-x, fmt)
    np.testing.assert_allclose(np.asarray(qn), -np.asarray(q), rtol=1e-6)


def test_pow2_scale_properties(key):
    x = jnp.abs(jax.random.normal(key, (100,))) + 1e-3
    s = pow2_scale(x)
    assert bool(jnp.all(s >= x))
    log = jnp.log2(s)
    np.testing.assert_allclose(np.asarray(log), np.round(np.asarray(log)),
                               atol=1e-6)


def test_per_channel_scale_shape(key):
    x = jax.random.normal(key, (4, 6, 8))
    s = compute_scale(x, axis=-1)
    assert s.shape == (1, 1, 8)
    s0 = compute_scale(x, axis=0)
    assert s0.shape == (4, 1, 1)


@given(st.integers(min_value=0, max_value=127),
       st.sampled_from([-1, 1]))
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(code, sign):
    fmt = LNSFormat(bits=8, gamma=8)
    c = jnp.asarray([[code]], fmt.code_dtype)
    s = jnp.asarray([[sign]], jnp.int8)
    packed = lns_pack(s, c, fmt)
    assert packed.dtype == jnp.uint8
    s2, c2 = lns_unpack(packed, fmt)
    assert int(s2[0, 0]) == sign and int(c2[0, 0]) == code


def test_pack_is_hardware_wire_format():
    """MSB = sign bit, low 7 bits = exponent code."""
    fmt = LNSFormat(bits=8, gamma=8)
    packed = lns_pack(jnp.asarray([-1], jnp.int8),
                      jnp.asarray([5], jnp.int8), fmt)
    assert int(packed[0]) == 128 + 5


def test_stochastic_rounding_unbiased(key):
    fmt = LNSFormat(bits=8, gamma=8, stochastic=True)
    x = jnp.full((20000,), 1.3456)
    scale = jnp.full((), 2.0)
    keys = jax.random.split(key, 1)[0]
    sign, code = lns_encode(x, fmt, scale, key=keys)
    dec = lns_decode(sign, code, fmt, scale)
    # E[2^-SR(e)/γ] != 2^(-e/γ) exactly (Jensen) but must straddle x
    lo = float(jnp.min(dec))
    hi = float(jnp.max(dec))
    assert lo < 1.3456 < hi


def test_quantization_gap_grows_with_magnitude():
    fmt = LNSFormat(bits=8, gamma=8)
    g = quantization_gap(jnp.asarray([0.1, 1.0, 10.0]), fmt)
    assert float(g[0]) < float(g[1]) < float(g[2])


def test_zero_and_flush_zero():
    fmt = LNSFormat(bits=8, gamma=8)
    s, c = lns_encode(jnp.zeros((3,)), fmt, jnp.ones(()))
    assert bool(jnp.all(c == fmt.max_code))  # clamps to smallest magnitude
    fz = LNSFormat(bits=8, gamma=8, flush_zero=True)
    dec = lns_decode(s, c, fz, jnp.ones(()))
    assert bool(jnp.all(dec == 0.0))


# ---------------------------------------------------------------------------
# narrowing re-grid: the self-speculative draft transform (DESIGN.md §11)


def test_with_bits_narrowing_halves_gamma():
    """Dropping wire bits halves the base factor per bit so the dynamic
    range survives — a B=6/7 draft spans the same magnitudes as B=8, just
    on a coarser exponent grid."""
    fmt = LNSFormat(bits=8, gamma=8)
    assert fmt.with_bits(7) == LNSFormat(bits=7, gamma=4)
    assert fmt.with_bits(6) == LNSFormat(bits=6, gamma=2)
    # range match is exact up to the max_code = 2^(B-1)-1 off-by-one,
    # which costs one coarse step: 15.75 at B=7, 15.5 at B=6 (vs 15.875)
    for bits in (6, 7):
        assert fmt.with_bits(bits).dynamic_range == pytest.approx(
            fmt.dynamic_range, rel=0.03)


@pytest.mark.parametrize("bits", [6, 7])
def test_requant_narrow_is_projection(key, bits):
    """Narrow -> widen -> narrow lands on the same coarse words: the
    draft view is a projection, so re-deriving it is lossless."""
    fmt8 = LNSFormat(bits=8, gamma=8)
    dst = fmt8.with_bits(bits)
    codes = jax.random.randint(key, (4096,), 0, fmt8.max_code + 1, jnp.int32)
    sign = jnp.where(jnp.arange(codes.size) % 2 == 0, 1, -1).astype(jnp.int8)
    packed = lns_pack(sign, codes, fmt8)
    down = lns_requant_packed(packed, fmt8, dst)
    up = lns_requant_packed(down, dst, fmt8)
    down2 = lns_requant_packed(up, fmt8, dst)
    np.testing.assert_array_equal(np.asarray(down), np.asarray(down2))


@pytest.mark.parametrize("bits", [6, 7])
def test_requant_monotone_and_sign_preserved(bits):
    """Exhaustive over the B=8 grid: the narrow code is monotone in the
    source code and the sign bit rides across untouched."""
    fmt8 = LNSFormat(bits=8, gamma=8)
    dst = fmt8.with_bits(bits)
    codes = jnp.arange(fmt8.max_code + 1, dtype=jnp.int32)
    for sval in (1, -1):
        sign = jnp.full(codes.shape, sval, jnp.int8)
        out = np.asarray(lns_requant_packed(
            lns_pack(sign, codes, fmt8), fmt8, dst))
        np.testing.assert_array_equal(out >> (dst.bits - 1),
                                      np.full(codes.shape, int(sval < 0)))
        assert np.all(np.diff(out & dst.max_code) >= 0)


@pytest.mark.parametrize("bits", [6, 7])
def test_requant_draft_decode_error_bound(bits):
    """Every un-clamped draft value sits within half a coarse grid step of
    its source value (the re-grid rounds the exponent to nearest)."""
    fmt8 = LNSFormat(bits=8, gamma=8)
    dst = fmt8.with_bits(bits)
    codes = jnp.arange(fmt8.max_code + 1, dtype=jnp.int32)
    sign = jnp.ones(codes.shape, jnp.int8)
    packed = lns_pack(sign, codes, fmt8)
    out = lns_requant_packed(packed, fmt8, dst)
    s, c = lns_unpack(out, dst)
    got = np.asarray(lns_decode(s, c, dst, jnp.ones(())))
    want = np.asarray(lns_decode(sign, codes, fmt8, jnp.ones(())))
    unclamped = np.asarray(c) < dst.max_code
    rel = np.abs(got - want) / want
    assert rel[unclamped].max() <= 2.0 ** (1.0 / (2 * dst.gamma)) - 1 + 1e-6


def test_format_validation():
    with pytest.raises(ValueError):
        LNSFormat(bits=8, gamma=3)
    with pytest.raises(ValueError):
        LNSFormat(bits=1, gamma=8)


# ---------------------------------------------------------------------------
# quantization_gap vs a brute-force nearest-code search (ISSUE-10: the
# Thm.-1 normalizer behind qerr_gap_ratio must be exact, per format)


@pytest.mark.parametrize("bits,gamma", [(4, 2), (5, 1), (6, 4), (8, 8),
                                        (8, 2), (12, 128), (16, 2048)])
def test_quantization_gap_bruteforce(bits, gamma):
    """On every on-grid magnitude, the closed form |x|·(2^(1/γ)-1) equals
    the distance to the next representable value found by brute-force
    search over the whole code grid."""
    fmt = LNSFormat(bits=bits, gamma=gamma)
    grid = np.exp2(-np.arange(fmt.max_code + 1, dtype=np.float64) / gamma)
    # e >= 1: code 0 is the top of the grid, nothing representable above
    for e in range(1, min(fmt.max_code + 1, 64)):
        v = grid[e]
        above = grid[grid > v * (1 + 1e-12)]
        brute = above.min() - v
        got = float(quantization_gap(jnp.asarray(v, jnp.float32), fmt))
        assert got == pytest.approx(brute, rel=1e-5), (e, got, brute)
    # off-grid points: the gap is the local grid spacing at that magnitude
    # (scales linearly — factor-of-2 shifts multiply it by exactly 2)
    x = jnp.asarray([0.3, 0.6, 1.2], jnp.float32)
    g = np.asarray(quantization_gap(x, fmt))
    assert g[1] == pytest.approx(2 * g[0], rel=1e-6)
    assert g[2] == pytest.approx(2 * g[1], rel=1e-6)


# ---------------------------------------------------------------------------
# with_bits keep_range semantics + the extreme 8 -> 4 re-grid drop


def test_with_bits_keep_range_both_directions():
    fmt = LNSFormat(bits=8, gamma=8)
    # widening: gamma scales 2x per bit, range preserved (§6.1.1)
    wide = fmt.with_bits(16)
    assert wide == LNSFormat(bits=16, gamma=2048)
    assert wide.dynamic_range == pytest.approx(fmt.dynamic_range, rel=0.01)
    # narrowing: gamma halves per dropped bit until it floors at 1
    assert fmt.with_bits(6) == LNSFormat(bits=6, gamma=2)
    assert fmt.with_bits(6).dynamic_range == pytest.approx(
        fmt.dynamic_range, rel=0.03)
    # extreme drop 8 -> 4: gamma would need 16x shrink but only has 8x —
    # it floors at 1 and the dynamic range shrinks (7.0 vs 15.875)
    tiny = fmt.with_bits(4)
    assert tiny == LNSFormat(bits=4, gamma=1)
    assert tiny.dynamic_range == pytest.approx(7.0)
    # keep_range=False pins gamma: same grid spacing, truncated range
    assert fmt.with_bits(4, keep_range=False) == LNSFormat(bits=4, gamma=8)
    assert fmt.with_bits(16, keep_range=False) == LNSFormat(bits=16, gamma=8)
    # round-tripping the bitwidth restores the original format
    assert fmt.with_bits(6).with_bits(8) == fmt


def test_requant_extreme_drop_sign_preserved_at_rails():
    """8 -> 4 bits (γ 8 -> 1, ratio 8): the sign bit must survive at BOTH
    rails and every coarse code stays in [0, 7] with the hi rail clamped."""
    fmt8 = LNSFormat(bits=8, gamma=8)
    dst = fmt8.with_bits(4)
    assert fmt8.gamma // dst.gamma == 8
    codes = jnp.arange(fmt8.max_code + 1, dtype=jnp.int32)
    for sval in (1, -1):
        sign = jnp.full(codes.shape, sval, jnp.int8)
        out = np.asarray(lns_requant_packed(
            lns_pack(sign, codes, fmt8), fmt8, dst))
        s, c = lns_unpack(jnp.asarray(out), dst)
        c = np.asarray(c)
        # sign rides across on every word, including both rail codes
        np.testing.assert_array_equal(np.asarray(s),
                                      np.full(codes.shape, sval))
        # overflow rail (code 0, largest magnitude) maps to coarse code 0
        assert c[0] == 0
        # underflow rail (code 127, smallest magnitude) clamps to dst max
        assert c[-1] == dst.max_code == 7
        assert c.min() >= 0 and c.max() <= dst.max_code
        assert np.all(np.diff(c) >= 0)  # monotone through the clamp
        # round-to-nearest on the un-clamped body: code 20 -> (20+4)//8;
        # code 60 re-grids past the rail and clamps
        assert c[20] == 3 and c[60] == dst.max_code
    # packed MSB check at the rails, directly on the wire word
    neg = np.asarray(lns_requant_packed(
        lns_pack(jnp.full((2,), -1, jnp.int8),
                 jnp.asarray([0, fmt8.max_code], jnp.int32), fmt8),
        fmt8, dst))
    assert np.all((neg >> (dst.bits - 1)) & 1 == 1)
