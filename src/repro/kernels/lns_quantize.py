"""Fused LNS encode+pack kernel — one pass from f32/bf16 to packed words.

Implements the paper's Q_log (Eq. 3) as the write-side of the TPU datapath:
``code = clamp(round(-log2(|x|/s)·γ), 0, 2^(B-1)-1)`` packed with the sign
bit into a single byte. Scales arrive per row tile (per-channel) or
broadcast (per-tensor) — the absmax reduction runs in a prior pass (the
hardware's PPU also scales as a post-processing step, §5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lns import LNSFormat, lns_requant_packed
from repro.kernels.dispatch import resolve_interpret

__all__ = ["lns_quantize_pallas", "lns_requant_pallas"]


def _kernel(x_ref, s_ref, out_ref, *, bits: int, gamma: int):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)  # (block_r, 1), broadcasts over cols
    max_code = (1 << (bits - 1)) - 1
    # int32 throughout: Mosaic has no f32 -> uint32 cast, and the word
    # (< 2^bits <= 256) is the same bit pattern in either
    neg = (x < 0).astype(jnp.int32)
    mag = jnp.abs(x) / s
    e = -jnp.log2(jnp.maximum(mag, jnp.finfo(jnp.float32).tiny)) * gamma
    e = jnp.clip(jnp.floor(e + 0.5), 0, max_code).astype(jnp.int32)
    out_ref[...] = ((neg << (bits - 1)) | e).astype(jnp.uint8)


def _requant_kernel(w_ref, out_ref, *, src: LNSFormat, dst: LNSFormat):
    # The kernel body IS the reference transform: lns_requant_packed is pure
    # integer bit-slicing, so tracing it inside the Pallas block keeps the
    # kernel and the jnp oracle one definition — they cannot drift.
    out_ref[...] = lns_requant_packed(w_ref[...], src, dst)


@functools.partial(
    jax.jit, static_argnames=("src", "dst", "block_r", "block_c", "interpret"))
def lns_requant_pallas(
    packed: jax.Array,
    src: LNSFormat,
    dst: LNSFormat,
    *,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Re-grid packed wire words ``(R, C)`` from ``src`` to ``dst`` bits.

    The draft-view transform of self-speculative decoding: integer-only
    exponent re-grid (upscale multiplies by the γ ratio, downscale rounds
    ties away from zero), sign bit repositioned to ``dst.bits - 1``. Scales
    are untouched — callers share them with the source weight.
    """
    assert src.bits <= 8 and dst.bits <= 8, "packed-byte wire format"
    R, C = packed.shape
    assert R % block_r == 0 and C % block_c == 0, (
        f"({R},{C}) must tile by ({block_r},{block_c})")

    interpret = resolve_interpret(interpret)
    grid = (R // block_r, C // block_c)
    kernel = functools.partial(_requant_kernel, src=src, dst=dst)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.uint8),
        interpret=interpret,
    )(packed)


@functools.partial(
    jax.jit, static_argnames=("fmt", "block_r", "block_c", "interpret"))
def lns_quantize_pallas(
    x: jax.Array,
    scale: jax.Array,
    fmt: LNSFormat,
    *,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Encode ``x (R,C)`` with per-row ``scale (R,1)`` into packed uint8.

    For per-tensor scaling pass ``jnp.full((R,1), s)``. ``fmt.bits`` must be
    <= 8 (the packed-byte wire format).
    """
    assert fmt.bits <= 8, "packed-byte kernel supports bits<=8"
    R, C = x.shape
    assert scale.shape == (R, 1), scale.shape
    assert R % block_r == 0 and C % block_c == 0, (
        f"({R},{C}) must tile by ({block_r},{block_c})")

    interpret = resolve_interpret(interpret)
    grid = (R // block_r, C // block_c)
    kernel = functools.partial(_kernel, bits=fmt.bits, gamma=fmt.gamma)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((block_r, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.uint8),
        interpret=interpret,
    )(x, scale)
