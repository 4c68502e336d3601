"""Attention: GQA (± bias / qk-norm / sliding window / softcap) and MLA.

Training/prefill attend through ``dispatch.flash_attend``: the fused Pallas
kernel (``kernels/flash_attend.py``) where it tiles the shape, else the jnp
oracle here, ``flash_attention`` (lax.scan over KV blocks with an online
softmax — logits never materialize beyond one (B,H,Sq,blk) tile). Heads
are sharded when num_heads divides the model axis; otherwise the query
sequence is (sequence-parallel fallback for head counts like 40/24/9),
which only the oracle serves.

Decode attends one query against the full cache with plain softmax; the
cache's sequence axis is sharded over the model axis (split-KV
flash-decode: GSPMD turns the softmax/PV reductions into tiny all-reduces),
which also serves the batch-1 ``long_500k`` shape by spreading 512k of KV
over the whole mesh.

MLA (deepseek-v3) keeps the paper-faithful low-rank projections; decode uses
the absorbed form so the cache stores only (c_kv, k_rope).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig, cot_boundary, qeinsum, ste_quantize
from repro.distributed.sharding import current_mesh, model_axis_size, shard
from repro.kernels import dispatch
from repro.models.common import ArchConfig, dense_init
from repro.models.layers import apply_rope, decoded_of, dense_of, rope

__all__ = ["attn_init", "attn_apply", "mla_init", "mla_apply",
           "init_kv_cache", "init_paged_kv_cache", "is_paged_cache",
           "flash_attention", "model_axis_size"]


def _full_mesh_size() -> int:
    mesh = current_mesh()
    return 1 if mesh is None else mesh.devices.size


def _qa(x, cfg: ArchConfig, qcfg: Optional[QuantConfig]):
    """Q_A on attention-internal GEMM operands (paper: all GEMMs quantized)."""
    if qcfg is not None and cfg.quantize_attention and qcfg.act is not None:
        return ste_quantize(x, qcfg.act, None)
    return x


def _mask(q_pos, k_pos, window: Optional[int]):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def _softcap(logits, cap: Optional[float]):
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def flash_attention(
    q: jax.Array,              # (B, Sq, H, D)
    k: jax.Array,              # (B, Skv, H, D)  (kv heads pre-repeated)
    v: jax.Array,              # (B, Skv, H, D)
    *,
    q_offset: int = 0,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
) -> jax.Array:
    """Causal online-softmax attention, scanning KV in blocks.

    ``v`` may have a different head width than q/k (MLA's v_head_dim).
    """
    B, Sq, H, D = q.shape
    Skv, Dv = k.shape[1], v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, Skv)
    assert Skv % block_k == 0, (Skv, block_k)
    nblk = Skv // block_k

    qf = cot_boundary(q).astype(jnp.float32) * scale
    kb = k.reshape(B, nblk, block_k, H, D).swapaxes(0, 1)
    vb = v.reshape(B, nblk, block_k, H, Dv).swapaxes(0, 1)
    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, blk_idx = xs
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            cot_boundary(k_blk).astype(jnp.float32))
        logits = _softcap(logits, softcap)
        mask = _mask(q_pos, k_pos, window)  # (Sq, blk)
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1)
        acc = corr[..., None] * acc + jnp.einsum(
            "bhqk,bkhd->bhqd", p, cot_boundary(v_blk).astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((B, H, Sq), -1e30, jnp.float32),
            jnp.zeros((B, H, Sq), jnp.float32),
            jnp.zeros((B, H, Sq, Dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # (B, Sq, H, D)


# ---------------------------------------------------------------------------
# GQA


def attn_init(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.compute_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _qk_norm(x, gain, eps):
    x = cot_boundary(x)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps) * (1.0 + gain)
    return x * scale.astype(x.dtype)


def _shard_qkv(q, k, v, heads_divisible: bool):
    if heads_divisible:
        q = shard(q, "batch", "seq", "act_heads", None)
        k = shard(k, "batch", "seq", "act_heads", None)
        v = shard(v, "batch", "seq", "act_heads", None)
    else:
        # head count doesn't divide the model axis: sequence-parallel
        # attention. (A batch-over-full-mesh reshard variant was measured
        # in §Perf and REFUTED — the attention-section all-to-alls cost
        # 3.4x the redundancy they remove; see EXPERIMENTS.md.)
        q = shard(q, "batch", "seq_shard", None, None)
        k = shard(k, "batch", "seq", None, None)
        v = shard(v, "batch", "seq", None, None)
    return q, k, v


def attn_apply(
    p: Dict[str, Any],
    x: jax.Array,                       # (B, S, D)
    cfg: ArchConfig,
    qcfg: Optional[QuantConfig],
    *,
    positions: jax.Array,               # (S,) absolute positions
    window: Optional[int] = None,
    theta: Optional[float] = None,
    cache: Optional[Dict[str, jax.Array]] = None,
    block_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """One attention block. With ``cache``, decode/append mode (S small);
    a paged cache additionally needs the engine's ``block_table``."""
    B, S, D = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    theta = theta if theta is not None else cfg.rope_theta

    q = qeinsum("bsd,de->bse", x, dense_of(p["wq"], cfg, qcfg), qcfg)
    k = qeinsum("bsd,de->bse", x, dense_of(p["wk"], cfg, qcfg), qcfg)
    v = qeinsum("bsd,de->bse", x, dense_of(p["wv"], cfg, qcfg), qcfg)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
    # positions may be (S,) or per-slot (B, S) — rope broadcasts either way
    rot = rope(positions, hd, theta)
    if rot.ndim == 3:
        rot = rot[None]  # (1, S, hd/2, 2)
    q = apply_rope(q, rot)
    k = apply_rope(k, rot)
    q, k, v = _qa(q, cfg, qcfg), _qa(k, cfg, qcfg), _qa(v, cfg, qcfg)

    if cache is None:
        # training / prefill: repeat KV to full heads and flash
        heads_div = h % model_axis_size() == 0
        rep = h // kv
        kf = jnp.repeat(k, rep, axis=2)
        vf = jnp.repeat(v, rep, axis=2)
        q, kf, vf = _shard_qkv(q, kf, vf, heads_div)
        with jax.named_scope("core"):
            out = dispatch.flash_attend(q, kf, vf, window=window,
                                        softcap=cfg.attn_logit_softcap)
        new_cache = None
    elif is_paged_cache(cache):
        assert window is None, "paged KV pools do not serve ring buffers"
        assert block_table is not None, "paged cache requires a block table"
        out, new_cache = _paged_attend(q, k, v, cache, cfg,
                                       block_table=block_table, qcfg=qcfg)
    else:
        with jax.named_scope("core"):  # the dense row write included
            out, cache = _decode_attend(q, k, v, cache, cfg, window=window)
        new_cache = cache

    out = out.reshape(B, S, h * hd)
    # row-parallel wo in training (attn_out -> model); serving rules resolve
    # attn_out to None, making this constraint the all-gather epilogue that
    # keeps the replicated wo contraction bitwise equal to single-device
    out = shard(out, "batch", "seq", "attn_out")
    out = qeinsum("bse,ed->bsd", out, dense_of(p["wo"], cfg, qcfg), qcfg)
    return shard(out, "batch", "seq", "embed"), new_cache


def init_kv_cache(batch: int, max_len: int, cfg: ArchConfig,
                  window: Optional[int] = None) -> Dict[str, jax.Array]:
    """Fixed-capacity KV cache; window layers allocate a ring buffer.

    With ``cfg.kv_cache_bits`` the cache stores packed LNS words (1 byte per
    element at 8 bits — half the HBM reads of bf16) plus a per-position
    per-head power-of-two scale; decode dequantizes on read.
    """
    cap = min(window, max_len) if window else max_len
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_cache_bits:
        return {
            "k": jnp.zeros((batch, cap, kv, hd), jnp.uint8),
            "v": jnp.zeros((batch, cap, kv, hd), jnp.uint8),
            "k_scale": jnp.ones((batch, cap, kv, 1), jnp.bfloat16),
            "v_scale": jnp.ones((batch, cap, kv, 1), jnp.bfloat16),
            "idx": jnp.zeros((batch,), jnp.int32),
        }
    dt = cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, cap, kv, hd), dt),
        "v": jnp.zeros((batch, cap, kv, hd), dt),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


def _kv_fmt(cfg: ArchConfig) -> LNSFormat:
    from repro.core.lns import LNSFormat
    return LNSFormat(bits=cfg.kv_cache_bits, gamma=8)


def _kv_encode(x: jax.Array, cfg: ArchConfig):
    """(B,S,KV,hd) -> packed codes + per-(pos,head) scale."""
    from repro.core.lns import compute_scale, lns_encode, lns_pack
    fmt = _kv_fmt(cfg)
    scale = compute_scale(x, axis=(0, 1, 2))  # keep all but head_dim
    sign, code = lns_encode(x, fmt, scale)
    bscale = jnp.broadcast_to(scale, x.shape[:-1] + (1,)).astype(jnp.bfloat16)
    return lns_pack(sign, code, fmt), bscale


def _kv_decode(packed: jax.Array, scale: jax.Array, cfg: ArchConfig):
    from repro.core.lns import lns_unpack, lns_decode
    fmt = _kv_fmt(cfg)
    sign, code = lns_unpack(packed, fmt)
    return lns_decode(sign, code, fmt, scale.astype(jnp.float32),
                      dtype=cfg.compute_dtype)


def init_paged_kv_cache(batch: int, num_pages: int, page_size: int,
                        cfg: ArchConfig) -> Dict[str, jax.Array]:
    """Block-paged KV pool shared by all slots of one attention layer.

    ``num_pages + 1`` pages of ``page_size`` tokens each (the extra page is
    the *null* page: unused block-table entries point at it, so gathers of a
    slot's unallocated tail and writes from freed slots land in one
    sacrificial page instead of corrupting live KV). Per-slot state is just
    the write cursor ``idx``; the page mapping lives in the engine-owned
    block table threaded through ``forward`` (same page ids for every
    layer — each layer indexes its own pool with them).

    Wire format matches the dense cache: with ``cfg.kv_cache_bits`` pages
    store packed LNS words + per-(pos, head) power-of-two scales
    (``_kv_encode``), decoded on read. Pages are kv-head-major,
    ``(P, kv, page, hd)``: one page is one whole-dims block for the paged
    attention kernel (see ``kernels/paged_attend.py``).
    """
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    P = num_pages + 1
    if cfg.kv_cache_bits:
        return {
            "kp": jnp.zeros((P, kv, page_size, hd), jnp.uint8),
            "vp": jnp.zeros((P, kv, page_size, hd), jnp.uint8),
            "kp_scale": jnp.ones((P, kv, page_size, 1), jnp.bfloat16),
            "vp_scale": jnp.ones((P, kv, page_size, 1), jnp.bfloat16),
            "idx": jnp.zeros((batch,), jnp.int32),
        }
    dt = cfg.compute_dtype
    return {
        "kp": jnp.zeros((P, kv, page_size, hd), dt),
        "vp": jnp.zeros((P, kv, page_size, hd), dt),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


def is_paged_cache(cache) -> bool:
    return isinstance(cache, dict) and "kp" in cache


def _paged_attend(q, k_new, v_new, cache, cfg: ArchConfig, *,
                  block_table: jax.Array,
                  qcfg: Optional[QuantConfig] = None):
    """Paged-pool decode/append: scatter the new KV into this slot's pages,
    then attend over the pages named by the block table.

    ``block_table`` is (B, max_pages) int32 — slot-local page index ``j``
    covers absolute positions ``[j*page_size, (j+1)*page_size)``. Unused
    entries point at the null page (see :func:`init_paged_kv_cache`), so
    out-of-range writes from recycled rows and the gathered-but-invalid
    tail are harmless (the tail is masked out before the softmax anyway).
    """
    B, S, h, hd = q.shape
    pool_k = cache["kp"]
    page = pool_k.shape[2]
    mp = block_table.shape[1]
    idx = cache["idx"]  # (B,) tokens already cached, per slot
    pos = idx[:, None] + jnp.arange(S)  # (B, S) absolute write positions
    pg = jnp.take_along_axis(block_table, jnp.clip(pos // page, 0, mp - 1),
                             axis=1)
    # positions past the slot's page span (right-padded prefill tails,
    # stale cursors of recycled rows) must not clamp onto a live page:
    # point them out of bounds and let the scatter drop them
    pg = jnp.where(pos < mp * page, pg, pool_k.shape[0])
    off = pos % page

    with jax.named_scope("kv_write"):
        quant = bool(cfg.kv_cache_bits)
        if quant:
            pk_new, sk_new = _kv_encode(k_new, cfg)
            pv_new, sv_new = _kv_encode(v_new, cfg)
            store = (("kp", pk_new), ("vp", pv_new),
                     ("kp_scale", sk_new), ("vp_scale", sv_new))
        else:
            store = (("kp", k_new), ("vp", v_new))

        new_cache = dict(cache)
        fpg, foff = pg.reshape(-1), off.reshape(-1)
        for key, new in store:
            flat = new.reshape((B * S,) + new.shape[2:])   # (B*S, kv, ·)
            new_cache[key] = cache[key].at[fpg, :, foff].set(
                flat.astype(cache[key].dtype), mode="drop")
            # pool pages stay head-sharded across the mesh model axis
            # (pages and page offsets are shard-local views of one logical
            # block table)
            new_cache[key] = shard(new_cache[key], None, "kv_heads", None,
                                   None)
        new_cache["idx"] = idx + S

    with jax.named_scope("core"):
        out = dispatch.paged_attend(
            q, new_cache["kp"], new_cache["vp"],
            new_cache.get("kp_scale"), new_cache.get("vp_scale"),
            block_table, idx + S,
            fmt=_kv_fmt(cfg) if quant else None,
            softcap=cfg.attn_logit_softcap,
            sm_scale=1.0 / math.sqrt(hd))
    return out.astype(q.dtype), new_cache


def _row_insert(buf, new, idx):
    """Per-row append: row b of ``new`` lands at ``buf[b, idx[b]:...]``.

    Each batch row is an independent serving slot with its own write
    cursor — the continuous-batching engine relies on this to hold
    sequences of different lengths in one cache."""
    def one(b, n, i):
        return jax.lax.dynamic_update_slice(b, n, (i,) + (0,) * (b.ndim - 1))
    return jax.vmap(one)(buf, new, idx)


def _decode_attend(q, k_new, v_new, cache, cfg: ArchConfig, *,
                   window: Optional[int]):
    """Append S new positions to the cache and attend over it (plain
    softmax; cache seq is sharded over the mesh => split-KV decode).

    ``cache["idx"]`` is (B,): every batch row (= serving slot) has its own
    sequence length, so a freed slot can restart from position 0 while its
    neighbours keep decoding."""
    B, S, h, hd = q.shape
    kv = cfg.num_kv_heads
    idx = cache["idx"]  # (B,) int32: tokens already cached, per slot
    cap = cache["k"].shape[1]
    slot = jnp.arange(cap)
    q_abs = idx[:, None] + jnp.arange(S)  # (B, S) absolute query positions

    quant = bool(cfg.kv_cache_bits)
    if quant:  # packed-LNS cache: encode the new keys once (beyond-paper)
        pk_new, sk_new = _kv_encode(k_new, cfg)
        pv_new, sv_new = _kv_encode(v_new, cfg)
        k_old = _kv_decode(cache["k"], cache["k_scale"], cfg)
        v_old = _kv_decode(cache["v"], cache["v_scale"], cfg)
        store_k, store_v = pk_new, pv_new
    else:
        k_old, v_old = cache["k"], cache["v"]
        store_k, store_v = k_new, v_new

    new_cache = dict(cache)
    if window:
        # Attend over [old ring contents ∪ new keys]: inserting first would
        # evict keys that earlier in-call queries still need. Ring slot s
        # holds absolute position p ≡ s (mod cap), p <= idx-1.
        last_prev = idx[:, None] - 1                       # (B, 1)
        abs_prev = last_prev - ((last_prev - slot[None, :]) % cap)  # (B, cap)
        k_att = jnp.concatenate([k_old, k_new], axis=1)
        v_att = jnp.concatenate([v_old, v_new], axis=1)
        abs_pos = jnp.concatenate([abs_prev, q_abs], axis=1)  # (B, cap+S)
        valid = jnp.concatenate(
            [abs_prev >= 0, jnp.ones((B, S), bool)], axis=1)

        def ring_update(buf, new):
            if S >= cap:
                start = (idx + S - cap) % cap
                return jax.vmap(
                    lambda n, s: jnp.roll(n, s, axis=0))(new[:, -cap:], start)
            slots = (idx[:, None] + jnp.arange(S)) % cap  # (B, S), may wrap
            return jax.vmap(lambda b, sl, n: b.at[sl].set(n))(buf, slots, new)

        new_cache["k"] = ring_update(cache["k"], store_k)
        new_cache["v"] = ring_update(cache["v"], store_v)
        if quant:
            new_cache["k_scale"] = ring_update(cache["k_scale"], sk_new)
            new_cache["v_scale"] = ring_update(cache["v_scale"], sv_new)
    else:
        new_cache["k"] = _row_insert(cache["k"], store_k, idx)
        new_cache["v"] = _row_insert(cache["v"], store_v, idx)
        if quant:
            new_cache["k_scale"] = _row_insert(cache["k_scale"], sk_new, idx)
            new_cache["v_scale"] = _row_insert(cache["v_scale"], sv_new, idx)
            k_att = _kv_decode(new_cache["k"], new_cache["k_scale"], cfg)
            v_att = _kv_decode(new_cache["v"], new_cache["v_scale"], cfg)
        else:
            k_att, v_att = new_cache["k"], new_cache["v"]
        abs_pos = jnp.broadcast_to(slot[None, :], (B, cap))
        valid = slot[None, :] < (idx[:, None] + S)
    # kv_seq wins the model axis under training rules (split-KV decode);
    # serving rules map kv_seq -> None so the same annotation head-shards
    for key in (("k", "v", "k_scale", "v_scale") if quant else ("k", "v")):
        new_cache[key] = shard(new_cache[key],
                               "batch", "kv_seq", "kv_heads", None)

    rep = h // kv
    kf = jnp.repeat(k_att, rep, axis=2)
    vf = jnp.repeat(v_att, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) / math.sqrt(hd)
    logits = _softcap(logits, cfg.attn_logit_softcap)
    mask = valid[:, None, :] & (abs_pos[:, None, :] <= q_abs[:, :, None])
    if window:
        mask &= abs_pos[:, None, :] > (q_abs[:, :, None] - window)
    logits = jnp.where(mask[:, None], logits, -1e30)  # (B,1,S,K) vs (B,h,S,K)
    p_attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p_attn, vf.astype(jnp.float32))
    new_cache["idx"] = idx + S
    return out.astype(q.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v3)


def mla_init(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rpe, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = cfg.compute_dtype
    ks = jax.random.split(key, 6)
    return {
        "q_down": dense_init(ks[0], d, qr, dt),
        "q_norm": jnp.zeros((qr,), jnp.float32),
        "q_up": dense_init(ks[1], qr, h * (nope + rpe), dt),
        "kv_down": dense_init(ks[2], d, kvr + rpe, dt),
        "kv_norm": jnp.zeros((kvr,), jnp.float32),
        "kv_up": dense_init(ks[3], kvr, h * (nope + vd), dt),
        "wo": dense_init(ks[4], h * vd, d, dt),
    }


def mla_apply(
    p: Dict[str, Any],
    x: jax.Array,
    cfg: ArchConfig,
    qcfg: Optional[QuantConfig],
    *,
    positions: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, D = x.shape
    h = cfg.num_heads
    nope, rpe, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    from repro.models.layers import rms_norm  # local import to avoid cycle

    ql = qeinsum("bsd,dr->bsr", x, dense_of(p["q_down"], cfg, qcfg), qcfg)
    ql = rms_norm(ql, p["q_norm"], cfg.norm_eps)
    q = qeinsum("bsr,re->bse", ql, dense_of(p["q_up"], cfg, qcfg), qcfg)
    q = q.reshape(B, S, h, nope + rpe)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kvd = qeinsum("bsd,dr->bsr", x, dense_of(p["kv_down"], cfg, qcfg), qcfg)
    c_kv, k_rope = kvd[..., :kvr], kvd[..., kvr:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)

    rot = rope(positions, rpe, cfg.rope_theta)
    if rot.ndim == 3:
        rot = rot[None]
    q_rope = apply_rope(q_rope, rot)
    k_rope = apply_rope(k_rope[:, :, None, :], rot)[:, :, 0, :]  # (B,S,rpe)

    if cache is None:
        kv_up = dense_of(p["kv_up"], cfg, qcfg)
        kv = qeinsum("bsr,re->bse", c_kv, kv_up, qcfg).reshape(B, S, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, h, rpe))],
            axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        qq, k, v = _qa(qq, cfg, qcfg), _qa(k, cfg, qcfg), _qa(v, cfg, qcfg)
        heads_div = h % model_axis_size() == 0
        qq, k, v = _shard_qkv(qq, k, v, heads_div)
        with jax.named_scope("core"):
            out = dispatch.flash_attend(qq, k, v,
                                        scale=1.0 / math.sqrt(nope + rpe))
        new_cache = None
    else:
        # absorbed decode folds kv_up into q/ctx einsums: dense view needed
        kv_up = decoded_of(p["kv_up"], cfg, qcfg)
        with jax.named_scope("core"):
            out, new_cache = _mla_decode(q_nope, q_rope, c_kv, k_rope,
                                         kv_up, cache, cfg)
    out = out.reshape(B, S, h * vd)
    out = qeinsum("bse,ed->bsd", out, dense_of(p["wo"], cfg, qcfg), qcfg)
    return shard(out, "batch", "seq", "embed"), new_cache


def init_mla_cache(batch: int, max_len: int, cfg: ArchConfig):
    dt = cfg.compute_dtype
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


def _mla_decode(q_nope, q_rope, c_kv_new, k_rope_new, kv_up, cache,
                cfg: ArchConfig):
    """Absorbed-form MLA decode: cache holds (c_kv, k_rope) only.
    ``cache["idx"]`` is (B,) — per-slot lengths, as in ``_decode_attend``."""
    B, S, h, nope = q_nope.shape
    kvr, vd = cfg.kv_lora_rank, cfg.v_head_dim
    idx = cache["idx"]  # (B,)
    ck = _row_insert(cache["c_kv"], c_kv_new, idx)
    kr = _row_insert(cache["k_rope"], k_rope_new, idx)
    ck = shard(ck, "batch", "kv_seq", None)
    kr = shard(kr, "batch", "kv_seq", None)
    cap = ck.shape[1]

    # absorb: q_nope (B,S,h,nope) x kv_up_k (kvr, h, nope) -> (B,S,h,kvr)
    kv_up_r = kv_up.reshape(kvr, h, nope + vd)
    w_k, w_v = kv_up_r[..., :nope], kv_up_r[..., nope:]
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                       w_k.astype(jnp.float32))
    logits = (jnp.einsum("bshr,bkr->bhsk", q_abs, ck.astype(jnp.float32))
              + jnp.einsum("bshr,bkr->bhsk", q_rope.astype(jnp.float32),
                           kr.astype(jnp.float32)))
    logits = logits / math.sqrt(nope + cfg.qk_rope_dim)
    slot = jnp.arange(cap)
    q_pos = idx[:, None] + jnp.arange(S)  # (B, S)
    mask = slot[None, None, :] <= q_pos[:, :, None]  # (B, S, cap)
    logits = jnp.where(mask[:, None], logits, -1e30)
    p_attn = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhsk,bkr->bshr", p_attn, ck.astype(jnp.float32))
    out = jnp.einsum("bshr,rhv->bshv", ctx, w_v.astype(jnp.float32))
    return out.astype(q_nope.dtype), {"c_kv": ck, "k_rope": kr, "idx": idx + S}
