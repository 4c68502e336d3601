"""Fused causal flash attention for training and dense prefill: forward,
and the flash backward as two kernels, under one ``jax.custom_vjp``.

The jnp oracle (``models.attention.flash_attention``) scans key blocks
with the whole ``(B, H, Sq, block)`` score tile in HBM, in f32, once per
pass. Here every score tile lives in VMEM only:

* **forward** (:func:`flash_attend_pallas`) — grid ``(B, H, q blocks,
  key blocks)``; an online softmax over the key axis with ``(m, l, acc)``
  in VMEM scratch. Returns the output and the per-row logsumexp (f32), the
  only residual the backward needs beside q, k, v and the output.
* **dK/dV** (:func:`flash_attend_dkv_pallas`) — grid ``(B, H, key blocks,
  q blocks)``; recomputes ``p^T = exp(k q^T - lse)`` per tile, so the
  logsumexp and ``di = rowsum(o * do)`` enter as lane-major rows.
* **dQ** (:func:`flash_attend_dq_pallas`) — grid ``(B, H, q blocks, key
  blocks)``; the same tile in query-major orientation.

Causal (and sliding-window) structure skips whole tiles: a key block that
lies wholly above the diagonal, or wholly outside the window, runs no
math, and its index map repeats the nearest live block, so Pallas issues
no DMA for it either. Masked entries contribute exact zeros, so skipping
them is the same arithmetic. Tiles wholly inside the visible band skip
the mask as well.

Precision: the MXU takes the operands in their own dtype (bf16 on the
training path; ``p`` and ``ds`` are cast to it) and accumulates in f32;
the softmax max, sum and logsumexp and ``softcap``'s tanh are f32. The
jnp path at DEFAULT precision on TPU feeds the MXU the same bf16 operands,
so the kernel differs from it in the order of accumulation only.

Layout: ``(B, H, S, D)`` inside; :func:`flash_attend` transposes from and
to the model's ``(B, S, H, D)`` at its edge. Block sizes come from the
sequence length (:func:`block_sizes`); ``kernels/dispatch.flash_attend``
takes this path only for shapes :func:`supported` accepts.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret

__all__ = ["flash_attend", "flash_attend_pallas", "flash_attend_dkv_pallas",
           "flash_attend_dq_pallas", "block_sizes", "supported"]

_NEG = -1e30          # the oracle's mask value
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def block_sizes(seq: int) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for a sequence of ``seq`` positions: the
    largest of 512, 256, 128 and of 1024, 512, 256, 128 dividing it (the
    fastest pair of the v5e sweep at 2048, PERF.md §6); a short sequence
    (under 128, a multiple of 8) is one block. ``None``: the kernel does
    not tile it."""
    if seq < 128:
        return (seq, seq) if seq % 8 == 0 else None
    pick = lambda sizes: next((b for b in sizes if seq % b == 0), None)
    bq, bk = pick((512, 256, 128)), pick((1024, 512, 256, 128))
    return None if bq is None else (bq, bk)


def supported(seq: int, head_dim: int, v_head_dim: int) -> bool:
    """Whether the kernel takes this shape: the sequence tiles by
    :func:`block_sizes` and both head widths are multiples of 8 up to 256
    (whole-array minor dims, so Mosaic's lane tiling holds at any of
    them)."""
    return (block_sizes(seq) is not None
            and all(d % 8 == 0 and 8 <= d <= 256
                    for d in (head_dim, v_head_dim)))


# ---------------------------------------------------------------------------
# tile geometry: which (q block, key block) pairs hold a visible entry


def _live(q_lo, k_lo, bq, bk, window):
    """Some query of the q block sees some key of the key block."""
    live = k_lo <= q_lo + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)
    return live


def _unmasked(q_lo, k_lo, bq, bk, window):
    """Every query of the q block sees every key of the key block."""
    full = k_lo + bk - 1 <= q_lo
    if window is not None:
        full = jnp.logical_and(full, k_lo > q_lo + bq - 1 - window)
    return full


def _key_range(i, bq, bk, nk, window):
    """First and last key block that query block ``i`` sees."""
    hi = (i * bq + bq - 1) // bk
    lo = 0 if window is None else jnp.maximum(i * bq - window + 1, 0) // bk
    return lo, jnp.minimum(hi, nk - 1)


def _query_range(j, bq, bk, nq, window):
    """First and last query block that sees key block ``j``."""
    lo = (j * bk) // bq
    hi = nq - 1 if window is None else jnp.minimum(
        (j * bk + bk - 1 + window - 1) // bq, nq - 1)
    return lo, hi


def _visible(shape, q_lo, k_lo, window, *, keys_major):
    """Causal/window mask of one tile, ``(bq, bk)`` or ``(bk, bq)``."""
    qa, ka = (1, 0) if keys_major else (0, 1)
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    vis = kpos <= qpos
    if window is not None:
        vis = jnp.logical_and(vis, kpos > qpos - window)
    return vis


def _logits(s, scale, softcap):
    """Scaled (and soft-capped) scores in f32; also the tanh for the
    backward (``None`` without a cap)."""
    s = s * scale
    if softcap is None:
        return s, None
    t = jnp.tanh(s / softcap)
    return softcap * t, t


def _both(live, unmasked, body):
    """Run ``body(masked)`` on live tiles: unmasked where the whole tile
    is visible, masked on the band's edges."""
    @pl.when(jnp.logical_and(live, unmasked))
    def _full():
        body(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(unmasked)))
    def _edge():
        body(True)


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, window, softcap):
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, j * bk

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def body(masked):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s, _ = _logits(jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32), scale, softcap)
        if masked:
            vis = _visible(s.shape, q_lo, k_lo, window, keys_major=False)
            s = jnp.where(vis, s, _NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(vis, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = corr * acc_sc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _both(_live(q_lo, k_lo, bq, bk, window),
          _unmasked(q_lo, k_lo, bq, bk, window), body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l))[:, 0][None, :]


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attend_pallas(q, k, v, *, scale: float, window: Optional[int],
                        softcap: Optional[float], block_q: int, block_k: int,
                        interpret: Optional[bool] = None):
    """``q (B, H, S, D)``, ``k (B, H, S, D)``, ``v (B, H, S, Dv)`` ->
    ``(o (B, H, S, Dv) in q.dtype, lse (B, H, 1, S) f32)``."""
    interpret = resolve_interpret(interpret)
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    nq, nk = S // block_q, S // block_k

    def q_ix(b, h, i, j):
        return b, h, i, 0

    def kv_ix(b, h, i, j):
        lo, hi = _key_range(i, block_q, block_k, nk, window)
        return b, h, jnp.clip(j, lo, hi), 0

    kernel = functools.partial(_fwd_kernel, scale=scale, window=window,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[pl.BlockSpec((None, None, block_q, D), q_ix),
                  pl.BlockSpec((None, None, block_k, D), kv_ix),
                  pl.BlockSpec((None, None, block_k, Dv), kv_ix)],
        out_specs=[pl.BlockSpec((None, None, block_q, Dv), q_ix),
                   pl.BlockSpec((None, None, 1, block_q),
                                lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, window, softcap):
    bk, bq = k_ref.shape[0], q_ref.shape[0]
    j, i = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, j * bk

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def body(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        # keys-major tile (bk, bq): lse and di broadcast as lane rows
        s, t = _logits(jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32), scale, softcap)
        if masked:
            vis = _visible(s.shape, q_lo, k_lo, window, keys_major=True)
            s = jnp.where(vis, s, _NEG)
        p = jnp.exp(s - lse_ref[...])
        dv_sc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_sc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _both(_live(q_lo, k_lo, bq, bk, window),
          _unmasked(q_lo, k_lo, bq, bk, window), body)

    @pl.when(i == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attend_dkv_pallas(q, k, v, do, lse, di, *, scale: float,
                            window: Optional[int], softcap: Optional[float],
                            block_q: int, block_k: int,
                            interpret: Optional[bool] = None):
    """dK and dV from the forward's logsumexp and ``di = rowsum(o * do)``
    (both ``(B, H, 1, S)`` f32); returns them in k's and v's dtypes."""
    interpret = resolve_interpret(interpret)
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    nq, nk = S // block_q, S // block_k

    def kv_ix(b, h, j, i):
        return b, h, j, 0

    def q_blk(j, i):
        lo, hi = _query_range(j, block_q, block_k, nq, window)
        return jnp.clip(i, lo, hi)

    def q_ix(b, h, j, i):
        return b, h, q_blk(j, i), 0

    def row_ix(b, h, j, i):
        return b, h, 0, q_blk(j, i)

    kernel = functools.partial(_dkv_kernel, scale=scale, window=window,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nk, nq),
        in_specs=[pl.BlockSpec((None, None, block_q, D), q_ix),
                  pl.BlockSpec((None, None, block_k, D), kv_ix),
                  pl.BlockSpec((None, None, block_k, Dv), kv_ix),
                  pl.BlockSpec((None, None, block_q, Dv), q_ix),
                  pl.BlockSpec((None, None, 1, block_q), row_ix),
                  pl.BlockSpec((None, None, 1, block_q), row_ix)],
        out_specs=[pl.BlockSpec((None, None, block_k, D), kv_ix),
                   pl.BlockSpec((None, None, block_k, Dv), kv_ix)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc, *,
               scale, window, softcap):
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, j * bk

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    def body(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s, t = _logits(jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32), scale, softcap)
        if masked:
            vis = _visible(s.shape, q_lo, k_lo, window, keys_major=False)
            s = jnp.where(vis, s, _NEG)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _both(_live(q_lo, k_lo, bq, bk, window),
          _unmasked(q_lo, k_lo, bq, bk, window), body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attend_dq_pallas(q, k, v, do, lse, di, *, scale: float,
                           window: Optional[int], softcap: Optional[float],
                           block_q: int, block_k: int,
                           interpret: Optional[bool] = None):
    """dQ from the forward's logsumexp and ``di``; in q's dtype."""
    interpret = resolve_interpret(interpret)
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    nq, nk = S // block_q, S // block_k

    def q_ix(b, h, i, j):
        return b, h, i, 0

    def kv_ix(b, h, i, j):
        lo, hi = _key_range(i, block_q, block_k, nk, window)
        return b, h, jnp.clip(j, lo, hi), 0

    row = pl.BlockSpec((None, None, 1, block_q),
                       lambda b, h, i, j: (b, h, 0, i))
    kernel = functools.partial(_dq_kernel, scale=scale, window=window,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[pl.BlockSpec((None, None, block_q, D), q_ix),
                  pl.BlockSpec((None, None, block_k, D), kv_ix),
                  pl.BlockSpec((None, None, block_k, Dv), kv_ix),
                  pl.BlockSpec((None, None, block_q, Dv), q_ix),
                  row, row],
        out_specs=pl.BlockSpec((None, None, block_q, D), q_ix),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# the differentiable op, in the model's (B, S, H, D) layout


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attend(q, k, v, scale, window, softcap, blocks, interpret):
    return _attend_fwd(q, k, v, scale, window, softcap, blocks, interpret)[0]


def _attend_fwd(q, k, v, scale, window, softcap, blocks, interpret):
    o, lse = flash_attend_pallas(q, k, v, scale=scale, window=window,
                                 softcap=softcap, block_q=blocks[0],
                                 block_k=blocks[1], interpret=interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(scale, window, softcap, blocks, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di[:, :, None, :]
    kw = dict(scale=scale, window=window, softcap=softcap, block_q=blocks[0],
              block_k=blocks[1], interpret=interpret)
    dk, dv = flash_attend_dkv_pallas(q, k, v, do, lse, di, **kw)
    dq = flash_attend_dq_pallas(q, k, v, do, lse, di, **kw)
    return dq, dk, dv


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 scale: Optional[float] = None, window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Causal attention of ``q (B, S, H, D)`` over ``k (B, S, H, D)``,
    ``v (B, S, H, Dv)`` (heads already repeated) -> ``(B, S, H, Dv)`` in
    q's dtype; differentiable. The shape must pass :func:`supported`."""
    S, D, Dv = q.shape[1], q.shape[-1], v.shape[-1]
    if k.shape[1] != S or not supported(S, D, Dv):
        raise ValueError(f"flash_attend does not tile q {q.shape}, "
                         f"k {k.shape}, v {v.shape}")
    scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    out = _attend(heads_major(q), heads_major(k), heads_major(v), scale,
                  window, softcap, block_sizes(S), resolve_interpret(interpret))
    return heads_major(out)
