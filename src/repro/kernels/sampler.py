"""Fused on-device sampler epilogue: scale -> gumbel add -> argmax in one
launch, eight logits rows by one vocab tile per grid step.

This is the kernel half of ``dispatch.fused_sample`` — the sort-free fast
path of ``server.sampling`` (pure greedy and temperature-only batches; the
rare top-k/top-p rows keep the jnp sort path). The gumbel noise comes in
as an *input*: it is drawn host-side with ``jax.random`` keys that fold in
the request seed and step, so a seeded request replays token-for-token
whether this kernel or the jnp reference serves it. (In-kernel
``pltpu.prng_*`` would also not be cross-backend reproducible, and is not
available in interpret mode — the CPU CI leg.)

Argmax is spelled manually as ``min(where(x == max(x), iota, V))`` —
first-maximum-wins, bit-identical to ``jnp.argmax`` / the host-side
``np.argmax`` the engine used before sampling moved on device, and it
lowers on Mosaic where a fused ``argmax`` reduction may not.

The caller (``ops.fused_sample``) pads V to a lane multiple with ``-1e30``
logits and zero gumbel, so padded columns can never win either reduction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret

__all__ = ["fused_sample_pallas"]


# rows per grid step (one f32 sublane tile) and the widest V tile: a
# (8, 8192) f32 block is 256 KiB, so the double-buffered inputs plus the
# body's temporaries stay far inside the default scoped VMEM at any vocab
ROWS = 8
MAX_TILE_V = 8192


def _tile_v(v: int) -> int:
    """Largest lane multiple <= MAX_TILE_V dividing ``v`` (itself a lane
    multiple): the vocab is walked in whole tiles, no ragged tail."""
    for t in range(min(v, MAX_TILE_V), 0, -128):
        if v % t == 0:
            return t
    raise ValueError(f"V={v} is not a multiple of 128")


def _argmax_first(x: jax.Array, base):
    """First-max-wins argmax of each row of a (ROWS, tile) block ->
    ``(max, index)``, both (ROWS, 1), index offset by the tile's ``base``
    column."""
    v = x.shape[-1]
    m = jnp.max(x, axis=-1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    i = jnp.min(jnp.where(x == m, idx, v), axis=-1, keepdims=True)
    return m, i + base


def _fold(m_ref, i_ref, m, i):
    """Merge a tile's (max, index) into the running pair. Strictly greater
    only: on a tie the earlier tile keeps the win (first-max-wins)."""
    better = m > m_ref[...]
    i_ref[...] = jnp.where(better, i, i_ref[...])
    m_ref[...] = jnp.where(better, m, m_ref[...])


def _kernel(temp_ref, lg_ref, *rest, with_gumbel):
    if with_gumbel:
        gum_ref, o_ref, gm, gi, sm, si = rest
    else:
        o_ref, gm, gi = rest
    j = pl.program_id(1)
    tile = lg_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        for m_ref, i_ref in ((gm, gi), (sm, si)) if with_gumbel else \
                ((gm, gi),):
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            i_ref[...] = jnp.zeros(i_ref.shape, jnp.int32)

    lg = lg_ref[...].astype(jnp.float32)            # (ROWS, tile)
    _fold(gm, gi, *_argmax_first(lg, j * tile))
    if with_gumbel:
        t = temp_ref[...]                             # (ROWS, 1)
        scaled = lg / jnp.maximum(t, 1e-6) + gum_ref[...].astype(jnp.float32)
        _fold(sm, si, *_argmax_first(scaled, j * tile))

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        tok = gi[...]
        if with_gumbel:
            tok = jnp.where(temp_ref[...] > 0.0, si[...], tok)
        o_ref[...] = jnp.broadcast_to(tok, o_ref.shape)  # lane-dense


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_sample_pallas(
    logits: jax.Array,                 # (B, V) — V already lane-padded
    gumbel: Optional[jax.Array],       # (B, V) or None for pure greedy
    temp: Optional[jax.Array],         # (B,) f32, required with gumbel
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One launch per batch: returns sampled token ids ``(B,) int32``.

    Grid ``(B / ROWS, V / tile)``: each step reduces a ``(ROWS, tile)``
    block into running per-row (max, index) pairs in VMEM, so every logit
    is read from HBM once at any vocab size. Rows pad to a ROWS multiple
    (pad rows are greedy over zeros and sliced off)."""
    interpret = resolve_interpret(interpret)
    B, V = logits.shape
    if gumbel is None:
        temp = jnp.zeros((B,), jnp.float32)  # fetched but unread
    pad = (-B) % ROWS
    temp = temp.astype(jnp.float32).reshape(B, 1)
    args = [temp, logits] + ([gumbel] if gumbel is not None else [])
    if pad:
        args = [jnp.pad(a, ((0, pad), (0, 0))) for a in args]
    tile = _tile_v(V)
    block = pl.BlockSpec((ROWS, tile), lambda r, j: (r, j))
    in_specs = [pl.BlockSpec((ROWS, 1), lambda r, j: (r, 0)), block]
    if gumbel is not None:
        in_specs.append(block)
    n_pairs = 2 if gumbel is not None else 1
    scratch = [pltpu.VMEM((ROWS, 1), dt)
               for _ in range(n_pairs) for dt in (jnp.float32, jnp.int32)]
    out = pl.pallas_call(
        functools.partial(_kernel, with_gumbel=gumbel is not None),
        grid=((B + pad) // ROWS, V // tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROWS, 128), lambda r, j: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad, 128), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:B, 0]
