"""Fused paged attention over a block-paged KV pool: pipelined page
fetches, tile-local LNS decode, online softmax — decode *and* prefill
shapes.

One kernel serves both serving shapes:

* **decode** — ``S == 1``: each slot's single query attends over the pages
  its block table names.
* **prefill over the block table** — ``S > 1``: the engine's batch-1
  suffix prefill. Queries sit at absolute positions ``lengths - S + s``
  (``pos_offset = n_cached`` for a prefix-cache hit), so the queries cover
  only the *suffix* while the gathered pages include the cached prefix —
  prefix-cached pages are attended but never recomputed, at kernel level
  rather than by re-gathering them into a scratch pool.

Pools are kv-head-major, ``(P, kv, page, hd)``, so one page is one
``(kv, page, hd)`` block whose trailing dims are whole array dims — the
Mosaic tiling rule holds at any head_dim (a manual page DMA out of a
``(P, page, kv, hd)`` pool needs ``kv`` and ``hd`` tile-aligned, which
smollm's 3x64 heads are not). The grid is ``(batch, max_pages)``: the
block table and per-slot lengths are scalar-prefetched into SMEM, and the
pools' index maps read the page id from the table, so Pallas's pipeline
fetches page ``i+1`` while page ``i`` is attended (see DESIGN.md §10).
Steps past a row's ``ceil(lengths[b] / page)`` resident pages repeat the
last resident page id — an unchanged block index issues no DMA — and skip
the math, so every resident page crosses HBM exactly once.

Packed LNS pages decode tile-locally in VMEM through the one shared
``core.lns.lns_decode_packed`` (scales applied per position/head), so the
kernel cannot drift from the jnp oracle. The online-softmax accumulator
``(m, l, acc)`` lives in VMEM scratch across the page axis; the full
``(S, positions)`` score row never materializes.

Invalid tail positions (beyond a slot's length) are masked before the
softmax, so block-table entries pointing at the pool's sacrificial null
page are harmless.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lns import LNSFormat, lns_decode_packed
from repro.kernels.dispatch import resolve_interpret

__all__ = ["paged_attend_pallas"]


def _resident_pages(ln, page, max_pages):
    """Pages holding a row's ``ln`` valid positions, >= 1 (the engine
    never serves an empty row: lengths include the token just written).
    Bucket-padded prefill queries can push ``ln`` past the table span —
    clamp to the table width (their outputs are discarded anyway)."""
    return jnp.minimum((ln + page - 1) // page, max_pages)


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest, fmt, softcap,
            sm_scale, seq):
    if fmt is not None:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, i = pl.program_id(0), pl.program_id(1)
    _, kv, rows, hd = q_ref.shape     # rows = seq * rep, s-major
    _, _, page, _ = k_ref.shape
    rep = rows // seq
    ln = len_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < _resident_pages(ln, page, tbl_ref.shape[1]))
    def _attend():
        k = k_ref[0]                          # (kv, page, hd)
        v = v_ref[0]
        if fmt is not None:
            # tile-local unpack+decode through the one shared definition
            # in core.lns — no drift from the jnp oracle
            k = lns_decode_packed(k, fmt, jnp.float32) * \
                ks_ref[0].astype(jnp.float32)
            v = lns_decode_packed(v, fmt, jnp.float32) * \
                vs_ref[0].astype(jnp.float32)
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)      # (kv, rows, hd)
        logits = jnp.einsum("grd,gpd->grp", q, k,
                            preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        # query row r sits at absolute position ln - seq + r // rep; the
        # causal test pos <= that is spelled multiplicatively,
        # (pos - ln + seq) * rep <= r, so no vector integer division
        row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        causal = (i * page + col - ln + seq) * rep <= row
        logits = jnp.where(causal, logits, -1e30)   # (kv, rows, page)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        pexp = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)              # (kv, rows, 1)
        l_ref[...] = corr * l_ref[...] + jnp.sum(pexp, axis=-1,
                                                 keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.einsum(
            "grp,gpd->grd", pexp, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "softcap", "sm_scale", "interpret"),
)
def paged_attend_pallas(
    q: jax.Array,            # (B, S, h, hd)
    kp: jax.Array,           # (P, kv, page, hd) packed words or dense
    vp: jax.Array,
    k_scale: Optional[jax.Array],   # (P, kv, page, 1) when fmt is set
    v_scale: Optional[jax.Array],
    block_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,      # (B,) int32 valid positions per slot
    *,
    fmt: Optional[LNSFormat] = None,
    softcap: Optional[float] = None,
    sm_scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged attention over a block-paged KV pool -> f32 (B, S, h, hd).

    ``lengths`` counts each slot's valid positions *including* the S just
    written, so query ``s`` sits at absolute position ``lengths - S + s``
    (matching ``dispatch._paged_attend_reference``). Must be >= 1 per row.

    Queries enter the kernel grouped by KV head, ``(B, kv, S*rep, hd)``
    (GQA stays grouped, never repeated), so each page is one batched
    ``(kv, rows, hd) x (kv, page, hd)`` matmul.
    """
    interpret = resolve_interpret(interpret)
    B, S, h, hd = q.shape
    _, kv, page, _ = kp.shape
    rep = h // kv
    rows = S * rep
    qg = q.reshape(B, S, kv, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, kv, rows, hd)

    max_pages = block_table.shape[1]

    def page_ix(b, i, tbl, ln):
        # past the resident pages, stay on the last one: no new DMA
        last = _resident_pages(ln[b], page, max_pages) - 1
        return (tbl[b, jnp.minimum(i, last)], 0, 0, 0)

    rows_spec = pl.BlockSpec((1, kv, rows, hd),
                             lambda b, i, tbl, ln: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec((1, kv, page, hd), page_ix)
    in_specs = [rows_spec, pool_spec, pool_spec]
    args = [qg, kp, vp]
    if fmt is not None:
        scale_spec = pl.BlockSpec((1, kv, page, 1), page_ix)
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=in_specs,
        out_specs=rows_spec,
        scratch_shapes=[pltpu.VMEM((kv, rows, 1), jnp.float32),
                        pltpu.VMEM((kv, rows, 1), jnp.float32),
                        pltpu.VMEM((kv, rows, hd), jnp.float32)],
    )
    kernel = functools.partial(_kernel, fmt=fmt, softcap=softcap,
                               sm_scale=sm_scale, seq=S)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, *args)
    return out.reshape(B, kv, S, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, S, h, hd)
