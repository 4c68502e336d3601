"""Fused LNS-Madam weight update (Algorithm 1) as a Pallas kernel.

One pass over (code, sign, grad, v) producing (code', v'): second-moment
EMA, bias-corrected normalization, and the integer exponent step
``code' = clamp(round(code + η·γ_U·g*·sign(W)))`` — all in VMEM, so the
update path touches each weight exactly once in HBM (read code+grad+v,
write code+v). No integer->LNS conversion anywhere (paper §4).

The bias-correction factor ``bc = 1 - β^t`` depends on the step count, so it
arrives as a (1,1) operand rather than a static constant.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lns import LNSFormat, quantization_gap
from repro.kernels.dispatch import resolve_interpret

__all__ = ["madam_update_pallas", "madam_update_packed_pallas",
           "madam_update_packed_stats_pallas", "madam_stats_vec",
           "madam_stats_dict", "requant_spec", "MADAM_STAT_KEYS",
           "MADAM_STAT_WIDTH"]

# numerics-telemetry epilogue (DESIGN.md §14): per-tile partial sums the
# stats kernel variant writes next to (word', v'). Layout of the width-8
# f32 vector (last two slots reserved):
#   0 sat_lo    count of steps rounding below code 0 (overflow rail clamp)
#   1 sat_hi    count of steps rounding above max_code (underflow rail)
#   2 dead      count of nonzero intended steps with zero code delta
#   3 qerr_sum  sum of |2^(-(code'-target)/γ) - 1| (realized vs ideal
#               multiplicative step, the paper's Thm.-1 quantity)
#   4 code_sum  sum of new codes (drift toward a rail shows as a trend)
#   5 req_hi    count of codes that will clamp when re-gridded to the
#               forward format (the B_U -> B_W requant clip site)
MADAM_STAT_KEYS = ("sat_lo", "sat_hi", "dead_frac", "qerr_rel",
                   "qerr_gap_ratio", "code_mean", "requant_sat_hi")
MADAM_STAT_WIDTH = 8
# the stats kernel's per-tile output block: the smallest f32 tile Mosaic
# accepts, holding the width-8 vector in row 0
_STAT_TILE = (8, 128)


def requant_spec(src: LNSFormat, dst: Optional[LNSFormat]):
    """Static ``(ratio, dst_max_code)`` for the forward re-grid stat, or
    ``None`` when the epilogue has nothing to count: no forward format,
    the identity re-grid (serving trains on the forward grid already), or
    a widening re-grid (finer grid, ``keep_range`` scales the ceiling)."""
    if dst is None:
        return None
    if (src.bits, src.gamma) == (dst.bits, dst.gamma):
        return None
    if dst.gamma >= src.gamma:
        return None
    return (src.gamma // dst.gamma, dst.max_code)


def madam_stats_terms(code, target, new_code, *, gamma: int, max_code: int,
                      requant=None):
    """The ``MADAM_STAT_WIDTH`` partial sums over one tile (or one whole
    leaf), as f32 scalars in the slot order above.

    Pure elementwise jnp + full reductions, so the same function traces
    inside the Pallas kernel body and in the jnp reference backend —
    counts are exact on both. Zero-padded tiles contribute exactly zero
    to every slot (pad words are code 0 with g=0, a fixed point).
    """
    codef = code.astype(jnp.float32)
    rounded = jnp.floor(target + 0.5)
    f32 = lambda m: m.astype(jnp.float32)
    n_lo = jnp.sum(f32(rounded < 0))
    n_hi = jnp.sum(f32(rounded > max_code))
    dead = jnp.sum(f32((new_code == codef) & (target != codef)))
    qerr = jnp.sum(jnp.abs(jnp.exp2(-(new_code - target) / gamma) - 1.0))
    code_sum = jnp.sum(new_code)
    zero = jnp.zeros((), jnp.float32)
    if requant is not None:
        ratio, dst_max = requant
        nc = new_code.astype(jnp.int32)
        req_hi = jnp.sum(f32((nc + ratio // 2) // ratio > dst_max))
    else:
        req_hi = zero
    return [n_lo, n_hi, dead, qerr, code_sum, req_hi, zero, zero]


def madam_stats_vec(code, target, new_code, *, gamma: int, max_code: int,
                    requant=None):
    """:func:`madam_stats_terms` stacked into a ``(MADAM_STAT_WIDTH,)``
    vector — the reference backend's form of the stat partials."""
    return jnp.stack(madam_stats_terms(code, target, new_code, gamma=gamma,
                                       max_code=max_code, requant=requant))


def madam_stats_dict(vec, n: int, fmt: LNSFormat,
                     requant_fmt: Optional[LNSFormat] = None):
    """Normalize a summed stat vector into the named per-leaf stats.

    ``qerr_gap_ratio`` divides the mean realized step error by the
    relative :func:`quantization_gap` at the leaf's format — the
    round-to-nearest floor is ~0.25 of the gap, so a ratio drifting far
    above that flags clipping/saturation rather than benign rounding.
    """
    del requant_fmt  # the static requant spec already shaped slot 5
    inv = 1.0 / float(max(n, 1))
    gap_rel = quantization_gap(jnp.ones((), jnp.float32), fmt)
    out = {
        "sat_lo": vec[0] * inv,
        "sat_hi": vec[1] * inv,
        "dead_frac": vec[2] * inv,
        "qerr_rel": vec[3] * inv,
        "code_mean": vec[4] * inv,
        "requant_sat_hi": vec[5] * inv,
    }
    out["qerr_gap_ratio"] = out["qerr_rel"] / gap_rel
    return out


def _step_math(code, sign, g, v, bc, *, lr, beta, eps, gamma, max_code):
    """Shared Algorithm-1 tile math: returns (new_code f32-rounded, new_v,
    target) — ``target`` is the pre-round/pre-clip exponent the stats
    epilogue compares the realized step against."""
    g = g.astype(jnp.float32)
    v = (1.0 - beta) * g * g + beta * v
    gstar = g * jax.lax.rsqrt(v / bc + eps)
    step = (lr * gamma) * gstar * sign.astype(jnp.float32)
    target = code.astype(jnp.float32) + step
    return jnp.clip(jnp.floor(target + 0.5), 0, max_code), v, target


def _kernel(bc_ref, code_ref, sign_ref, g_ref, v_ref, code_out, v_out, *,
            lr: float, beta: float, eps: float, gamma: int, max_code: int):
    code, v, _ = _step_math(code_ref[...], sign_ref[...], g_ref[...],
                            v_ref[...], bc_ref[0, 0], lr=lr, beta=beta,
                            eps=eps, gamma=gamma, max_code=max_code)
    code_out[...] = code.astype(code_out.dtype)
    v_out[...] = v


def _packed_kernel(bc_ref, w_ref, g_ref, v_ref, w_out, v_out, *,
                   lr: float, beta: float, eps: float, gamma: int, bits: int):
    """Packed-word variant: unpack, step, repack — all in VMEM, so the
    update reads/writes exactly one wire word per weight in HBM."""
    max_code = (1 << (bits - 1)) - 1
    w = w_ref[...].astype(jnp.int32)
    sign_bit = (w >> (bits - 1)) & 1
    code, v, _ = _step_math(w & max_code, 1 - 2 * sign_bit, g_ref[...],
                            v_ref[...], bc_ref[0, 0], lr=lr, beta=beta,
                            eps=eps, gamma=gamma, max_code=max_code)
    w_out[...] = ((sign_bit << (bits - 1)) | code.astype(jnp.int32)
                  ).astype(w_out.dtype)
    v_out[...] = v


def _packed_stats_kernel(bc_ref, w_ref, g_ref, v_ref, w_out, v_out,
                         stats_out, *, lr: float, beta: float, eps: float,
                         gamma: int, bits: int, requant):
    """Packed update + numerics epilogue: the stat partial sums are taken
    while (code, target, code') are live in VMEM — no second HBM pass."""
    max_code = (1 << (bits - 1)) - 1
    w = w_ref[...].astype(jnp.int32)
    sign_bit = (w >> (bits - 1)) & 1
    code = w & max_code
    new_code, v, target = _step_math(code, 1 - 2 * sign_bit, g_ref[...],
                                     v_ref[...], bc_ref[0, 0], lr=lr,
                                     beta=beta, eps=eps, gamma=gamma,
                                     max_code=max_code)
    w_out[...] = ((sign_bit << (bits - 1)) | new_code.astype(jnp.int32)
                  ).astype(w_out.dtype)
    v_out[...] = v
    # one (8, 128) f32 tile per grid step: slot k of the stat vector in
    # row 0, lane k, zeros elsewhere (a tile-aligned, lane-dense block)
    terms = madam_stats_terms(code, target, new_code, gamma=gamma,
                              max_code=max_code, requant=requant)
    row = jax.lax.broadcasted_iota(jnp.int32, _STAT_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, _STAT_TILE, 1)
    tile = jnp.zeros(_STAT_TILE, jnp.float32)
    for k, t in enumerate(terms):
        tile = jnp.where((row == 0) & (lane == k), t, tile)
    stats_out[0] = tile


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "lr", "beta", "eps", "block_r", "block_c",
                     "interpret"),
)
def madam_update_pallas(
    code: jax.Array,
    sign: jax.Array,
    g: jax.Array,
    v: jax.Array,
    count: jax.Array,
    fmt: LNSFormat,
    *,
    lr: float,
    beta: float = 0.999,
    eps: float = 1e-30,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
):
    """Fused Madam step on 2-D LNS weights. Returns (new_code, new_v).

    ``count`` is the post-increment step (>= 1) used for bias correction.
    ``interpret=None`` auto-detects the platform (compiled on real TPU).
    """
    interpret = resolve_interpret(interpret)
    R, C = code.shape
    assert sign.shape == (R, C) and g.shape == (R, C) and v.shape == (R, C)
    assert R % block_r == 0 and C % block_c == 0, (
        f"({R},{C}) must tile by ({block_r},{block_c})")

    bc = (1.0 - beta ** count.astype(jnp.float32)).reshape(1, 1)
    grid = (R // block_r, C // block_c)
    tile = lambda i, j: (i, j)
    kernel = functools.partial(
        _kernel, lr=lr, beta=beta, eps=eps, gamma=fmt.gamma,
        max_code=fmt.max_code)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), code.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
    )(bc, code, sign, g, v)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "lr", "beta", "eps", "block_r", "block_c",
                     "interpret"),
)
def madam_update_packed_pallas(
    packed: jax.Array,
    g: jax.Array,
    v: jax.Array,
    count: jax.Array,
    fmt: LNSFormat,
    *,
    lr: float,
    beta: float = 0.999,
    eps: float = 1e-30,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
):
    """Fused Madam step on *packed wire words* — the production update.

    Reads (word, grad, v) and writes (word', v') in one HBM pass; the sign
    bit never leaves the word (multiplicative updates preserve sign), so
    the parameter traffic is 1 byte/element each way at B<=8. Returns
    ``(new_packed, new_v)``.
    """
    interpret = resolve_interpret(interpret)
    R, C = packed.shape
    assert g.shape == (R, C) and v.shape == (R, C), (packed.shape, g.shape,
                                                     v.shape)
    assert R % block_r == 0 and C % block_c == 0, (
        f"({R},{C}) must tile by ({block_r},{block_c})")

    bc = (1.0 - beta ** count.astype(jnp.float32)).reshape(1, 1)
    grid = (R // block_r, C // block_c)
    tile = lambda i, j: (i, j)
    kernel = functools.partial(
        _packed_kernel, lr=lr, beta=beta, eps=eps, gamma=fmt.gamma,
        bits=fmt.bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), packed.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
    )(bc, packed, g, v)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "lr", "beta", "eps", "requant", "block_r",
                     "block_c", "interpret"),
)
def madam_update_packed_stats_pallas(
    packed: jax.Array,
    g: jax.Array,
    v: jax.Array,
    count: jax.Array,
    fmt: LNSFormat,
    *,
    lr: float,
    beta: float = 0.999,
    eps: float = 1e-30,
    requant=None,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
):
    """Packed Madam step with the numerics-stat epilogue fused in.

    Identical (word', v') to :func:`madam_update_packed_pallas` plus a
    summed ``(MADAM_STAT_WIDTH,)`` f32 stat vector (layout at the top of
    this module). Each tile writes its partial sums into row 0 of its own
    (8, 128) output block and the per-tile blocks are reduced here — the weights and grads are
    still touched exactly once in HBM. ``requant`` is the static
    ``requant_spec(...)`` tuple or ``None``. Returns
    ``(new_packed, new_v, stats_vec)``.
    """
    interpret = resolve_interpret(interpret)
    R, C = packed.shape
    assert g.shape == (R, C) and v.shape == (R, C), (packed.shape, g.shape,
                                                     v.shape)
    assert R % block_r == 0 and C % block_c == 0, (
        f"({R},{C}) must tile by ({block_r},{block_c})")

    bc = (1.0 - beta ** count.astype(jnp.float32)).reshape(1, 1)
    gr, gc = R // block_r, C // block_c
    tile = lambda i, j: (i, j)
    kernel = functools.partial(
        _packed_stats_kernel, lr=lr, beta=beta, eps=eps, gamma=fmt.gamma,
        bits=fmt.bits, requant=requant)
    new_packed, new_v, stats = pl.pallas_call(
        kernel,
        grid=(gr, gc),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((block_r, block_c), tile),
            pl.BlockSpec((1,) + _STAT_TILE, lambda i, j: (i * gc + j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), packed.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
            jax.ShapeDtypeStruct((gr * gc,) + _STAT_TILE, jnp.float32),
        ],
        interpret=interpret,
    )(bc, packed, g, v)
    return new_packed, new_v, stats[:, 0, :MADAM_STAT_WIDTH].sum(axis=0)
