"""The decoder-only LM covering all ten assigned architectures.

``ArchConfig.layer_pattern()`` describes the trunk as ``prefix`` unrolled
layers + ``n_periods`` scanned repetitions of a block period; this module
initializes parameters in exactly that structure (period params stacked on a
leading axis) and applies them with ``jax.lax.scan`` so 48-81-layer models
compile as one rolled loop. Weight leaves may be LNS codes — they are
decoded per layer *inside* the scan body, so at most one layer's dense
weights exist at a time (the no-fp-master-copy property, paper §4).

Families:
  dense/local/global — GQA attention + gated MLP (gemma3/qwen/granite/
    smollm/phi3v/musicgen backbones)
  moe   — attention (GQA or MLA) + routed experts (+ optional MTP head)
  mamba — Mamba2 SSD block (zamba2 trunk)
  shared_attn — zamba2's single shared transformer block, re-applied with a
    per-occurrence LoRA on the fused QKV projection
  rwkv  — RWKV6 time-mix + channel-mix
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizer import QuantConfig, cot_boundary, qeinsum
from repro.distributed.sharding import shard
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ArchConfig, dense_init
from repro.models.layers import (decoded_of, dense_of, embedding_init,
                                 mlp_apply, mlp_init, rms_norm)

__all__ = ["ForwardOut", "init_params", "forward", "lm_loss", "init_caches",
           "decode_step"]


class ForwardOut(NamedTuple):
    logits: jax.Array
    caches: Optional[Dict[str, Any]]
    aux: jax.Array
    hidden: jax.Array


# ---------------------------------------------------------------------------
# init


def _block_init(key, cfg: ArchConfig, kind: str) -> Dict[str, Any]:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    ln = lambda: jnp.zeros((d,), jnp.float32)
    if kind in ("dense", "local", "global"):
        a = (attn_mod.mla_init if cfg.use_mla else attn_mod.attn_init)(ks[0], cfg)
        return {"ln1": ln(), "attn": a, "ln2": ln(), "mlp": mlp_init(ks[1], cfg)}
    if kind == "moe":
        a = (attn_mod.mla_init if cfg.use_mla else attn_mod.attn_init)(ks[0], cfg)
        return {"ln1": ln(), "attn": a, "ln2": ln(),
                "moe": moe_mod.moe_init(ks[1], cfg)}
    if kind == "mamba":
        return {"ln": ln(), "mamba": ssm_mod.mamba_init(ks[0], cfg)}
    if kind == "rwkv":
        return {"ln1": ln(), "ln2": ln(), "rwkv": rwkv_mod.rwkv_init(ks[0], cfg)}
    if kind == "shared_attn":
        # per-occurrence LoRA only; the shared weights live at the top level
        r = cfg.shared_block_lora_rank
        out_dim = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
        p = {"ln1": ln(), "ln2": ln()}
        if r:
            p["lora_a"] = dense_init(ks[0], d, r, cfg.compute_dtype)
            p["lora_b"] = jnp.zeros((r, out_dim), cfg.compute_dtype)
        return p
    raise ValueError(kind)


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    prefix, n_periods, period = cfg.layer_pattern()
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {"embed": embedding_init(ks[0], cfg)}
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)

    if prefix:
        pk = jax.random.split(ks[1], len(prefix))
        params["prefix"] = [
            _block_init(pk[i], cfg, kind) for i, kind in enumerate(prefix)]

    if n_periods:
        period_params = {}
        for pos, kind in enumerate(period):
            pk = jax.random.split(jax.random.fold_in(ks[2], pos), n_periods)
            period_params[f"pos{pos}"] = jax.vmap(
                lambda k: _block_init(k, cfg, kind))(pk)
        params["period"] = period_params

    if "shared_attn" in period or "shared_attn" in prefix:
        params["shared"] = {
            "attn": attn_mod.attn_init(ks[3], cfg),
            "mlp": mlp_init(ks[4], cfg),
        }

    if cfg.mtp_depth:  # deepseek multi-token prediction module
        params["mtp"] = {
            "block": _block_init(ks[5], cfg, "dense"),
            "norm": jnp.zeros((cfg.d_model,), jnp.float32),
            "proj": dense_init(jax.random.fold_in(ks[5], 1),
                               2 * cfg.d_model, cfg.d_model, cfg.compute_dtype),
        }
    return params


# ---------------------------------------------------------------------------
# embedding / head


def _embed(params, tokens, cfg: ArchConfig, qcfg) -> jax.Array:
    # lookup semantics: the table must be dense (decoded per call, not a
    # persistent master copy)
    tok_table = decoded_of(params["embed"]["tok"], cfg, qcfg)
    if cfg.num_codebooks:
        # musicgen: sum the per-codebook embeddings (tokens: (B,S,Books))
        offsets = jnp.arange(cfg.num_codebooks) * cfg.vocab_size
        x = jnp.sum(jnp.take(tok_table, tokens + offsets, axis=0), axis=2)
    else:
        x = jnp.take(tok_table, tokens, axis=0)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return shard(x.astype(cfg.compute_dtype), "batch", "seq", "embed")


def _logits(params, x, cfg: ArchConfig, qcfg) -> jax.Array:
    if cfg.tie_embeddings:
        w = decoded_of(params["embed"]["tok"], cfg, qcfg).T
    else:
        w = dense_of(params["embed"]["head"], cfg, qcfg)
    logits = qeinsum("bsd,dv->bsv", x, w, qcfg)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# blocks


def _attn_kind_args(cfg: ArchConfig, kind: str):
    window = cfg.sliding_window if kind == "local" else None
    theta = (cfg.rope_theta_global or cfg.rope_theta) if kind == "global" \
        else cfg.rope_theta
    return window, theta


def _block_apply(kind: str, bp, x, cfg: ArchConfig, qcfg, *, positions,
                 shared=None, cache=None, block_tables=None):
    """Apply one block. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "local", "global", "moe"):
        window, theta = _attn_kind_args(cfg, kind)
        with jax.named_scope("attention"):
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            if cfg.use_mla:
                a, cache = attn_mod.mla_apply(bp["attn"], h, cfg, qcfg,
                                              positions=positions,
                                              cache=cache)
            else:
                a, cache = attn_mod.attn_apply(
                    bp["attn"], h, cfg, qcfg, positions=positions,
                    window=window, theta=theta, cache=cache,
                    block_table=block_tables)
            x = x + a
        with jax.named_scope("mlp"):
            h = rms_norm(x, bp["ln2"], cfg.norm_eps)
            if kind == "moe":
                m, aux = moe_mod.moe_apply(bp["moe"], h, cfg, qcfg)
            else:
                m = mlp_apply(bp["mlp"], h, cfg, qcfg)
            x = x + m
        return x, cache, aux

    if kind == "mamba":
        h = rms_norm(x, bp["ln"], cfg.norm_eps)
        m, cache = ssm_mod.mamba_apply(bp["mamba"], h, cfg, qcfg, state=cache)
        return x + m, cache, aux

    if kind == "rwkv":
        x, cache = _rwkv_block(bp, x, cfg, qcfg, cache)
        return x, cache, aux

    if kind == "shared_attn":
        sp = shared
        with jax.named_scope("attention"):
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            attn_p = dict(sp["attn"])
            if "lora_a" in bp:
                attn_p = _lora_qkv(attn_p, bp, h, cfg, qcfg)
            a, cache = attn_mod.attn_apply(attn_p, h, cfg, qcfg,
                                           positions=positions, cache=cache,
                                           block_table=block_tables)
            x = x + a
        with jax.named_scope("mlp"):
            m = mlp_apply(sp["mlp"], rms_norm(x, bp["ln2"], cfg.norm_eps),
                          cfg, qcfg)
            x = x + m
        return x, cache, aux

    raise ValueError(kind)


def _rwkv_block(bp, x, cfg, qcfg, cache):
    """RWKV residual wiring (parallel-block form): both halves read the
    pre-block residual (x += TM(ln1(x)) + CM(ln2(x))). The reference impl
    feeds CM the post-TM residual; the parallel form lets one rwkv_apply
    share the state dict — deviation noted in DESIGN.md §Deviations."""
    (tm, cm), new_cache = rwkv_mod.rwkv_apply(
        bp["rwkv"],
        rms_norm(x, bp["ln1"], cfg.norm_eps),
        rms_norm(x, bp["ln2"], cfg.norm_eps),
        cfg, qcfg, state=cache)
    return x + tm + cm, new_cache


def _lora_qkv(attn_p, bp, h, cfg: ArchConfig, qcfg):
    """zamba2: add a per-occurrence LoRA delta to the fused QKV weights."""
    # weight arithmetic: the shared QKV must be dense to take the delta
    a = decoded_of(bp["lora_a"], cfg, qcfg)
    b = decoded_of(bp["lora_b"], cfg, qcfg)
    delta = jnp.einsum("dr,re->de", a, b)  # (d, (h+2kv)*hd)
    hd = cfg.head_dim
    q_dim = cfg.num_heads * hd
    kv_dim = cfg.num_kv_heads * hd
    attn_p = dict(attn_p)
    attn_p["wq"] = decoded_of(attn_p["wq"], cfg, qcfg) + delta[:, :q_dim]
    attn_p["wk"] = decoded_of(attn_p["wk"], cfg, qcfg) + delta[:, q_dim:q_dim + kv_dim]
    attn_p["wv"] = decoded_of(attn_p["wv"], cfg, qcfg) + delta[:, q_dim + kv_dim:]
    return attn_p


# ---------------------------------------------------------------------------
# forward


def forward(
    params,
    tokens: jax.Array,
    cfg: ArchConfig,
    qcfg: Optional[QuantConfig] = None,
    *,
    patches: Optional[jax.Array] = None,   # phi3v precomputed patch embeds
    caches: Optional[Dict[str, Any]] = None,
    pos_offset: jax.Array | int = 0,
    block_tables: Optional[jax.Array] = None,
    remat: bool = False,
    scan_unroll: int | bool = 1,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Run the trunk. Returns (logits, new_caches, aux_loss).

    ``scan_unroll`` is forwarded to ``lax.scan`` over the layer periods;
    the dry-run passes ``True`` (full unroll) because XLA's cost analysis
    counts a while-loop body once — rolled scans stay the production path.

    ``tokens``: (B, S) int32 — or (B, S, Books) for multi-codebook audio.
    With ``caches`` the call is incremental (decode/chunked prefill).
    ``pos_offset`` may be a scalar or a (B,) vector of per-slot offsets —
    the serving engine decodes a batch whose rows sit at different
    sequence positions.

    ``block_tables`` (B, max_pages) maps each slot's local pages into the
    per-layer paged KV pools (see ``attention.init_paged_kv_cache``); one
    table serves every paged layer — each indexes its own pool with the
    same page ids. Required iff ``caches`` contains paged layers.
    """
    prefix, n_periods, period = cfg.layer_pattern()
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, qcfg)
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    off = jnp.asarray(pos_offset)
    positions = (off[..., None] + jnp.arange(S)).astype(jnp.int32)
    if positions.ndim > 1 and positions.shape[0] == 1:
        positions = positions[0]

    aux_total = jnp.zeros((), jnp.float32)
    shared = params.get("shared")
    new_caches: Dict[str, Any] = {}

    def body_fn(kind, bp, h, pos, sh, c):
        return _block_apply(kind, bp, h, cfg, qcfg, positions=pos,
                            shared=sh, cache=c, block_tables=block_tables)

    if remat:
        body_fn = jax.checkpoint(
            body_fn, static_argnums=(0,),
            policy=jax.checkpoint_policies.nothing_saveable)

    # ---- unrolled prefix
    if prefix:
        new_caches["prefix"] = []
        for i, kind in enumerate(prefix):
            c = caches["prefix"][i] if caches is not None else None
            x, c, aux = body_fn(kind, params["prefix"][i], x, positions,
                                shared, c)
            aux_total = aux_total + aux
            new_caches["prefix"].append(c)

    # ---- scanned periods
    if n_periods:
        pp = params["period"]
        pc = caches["period"] if caches is not None else None

        def scan_body(carry, xs):
            h, aux_acc = carry
            layer_params, layer_caches = xs
            out_caches = {}
            for pos, kind in enumerate(period):
                c = layer_caches[f"pos{pos}"] if layer_caches is not None else None
                h, c, aux = body_fn(kind, layer_params[f"pos{pos}"], h,
                                    positions, shared, c)
                aux_acc = aux_acc + aux
                out_caches[f"pos{pos}"] = c
            return (h, aux_acc), (out_caches if layer_caches is not None else 0)

        (x, aux_total), ys = jax.lax.scan(scan_body, (x, aux_total),
                                          (pp, pc), unroll=scan_unroll)
        if caches is not None:
            new_caches["period"] = ys

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(params, x, cfg, qcfg)
    return ForwardOut(logits, (new_caches if caches is not None else None),
                      aux_total, x)


def lm_loss(params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            qcfg: Optional[QuantConfig] = None, *, remat: bool = True,
            scan_unroll: int | bool = 1):
    """Next-token cross entropy (+ MoE aux + optional MTP loss)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    out = forward(params, tokens, cfg, qcfg, patches=batch.get("patches"),
                  remat=remat, scan_unroll=scan_unroll)
    logits, hidden = out.logits, out.hidden
    if batch.get("patches") is not None:
        n_patch = batch["patches"].shape[1]
        logits = logits[:, n_patch:]   # text positions only
        hidden = hidden[:, n_patch:]

    with jax.named_scope("head"):
        if cfg.num_codebooks:
            B, S, K = labels.shape
            logits = logits.reshape(B, S, K, cfg.vocab_size)
        loss = _xent(logits, labels) + 0.01 * out.aux

    if cfg.mtp_depth and "mtp" in params:
        loss = loss + 0.3 * _mtp_loss(params, hidden, tokens, labels, cfg, qcfg)
    return loss


def _xent(logits, labels):
    lf = cot_boundary(logits).astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _mtp_loss(params, hidden, tokens, labels, cfg: ArchConfig, qcfg):
    """Depth-1 multi-token prediction (deepseek-v3 MTP), sharing the head.

    Combines the trunk's hidden state at t with the embedding of token t+1
    through a projection + one extra block; the shared head predicts t+2.
    """
    emb = _embed(params, tokens, cfg, qcfg)
    emb_next = jnp.concatenate([emb[:, 1:], emb[:, -1:]], axis=1)
    h = rms_norm(hidden, params["mtp"]["norm"], cfg.norm_eps)
    x = qeinsum("bsd,dc->bsc",
                jnp.concatenate([h, emb_next], axis=-1),
                dense_of(params["mtp"]["proj"], cfg, qcfg), qcfg)
    x, _, _ = _block_apply("dense", params["mtp"]["block"], x, cfg, qcfg,
                           positions=jnp.arange(x.shape[1]))
    mtp_logits = _logits(params, x, cfg, qcfg)
    shifted = jnp.concatenate(
        [labels[:, 1:], -jnp.ones_like(labels[:, :1])], axis=1)
    return _xent(mtp_logits, shifted)


# ---------------------------------------------------------------------------
# serving


def init_caches(batch: int, max_len: int, cfg: ArchConfig, *,
                page_size: Optional[int] = None,
                num_pages: Optional[int] = None,
                window_slack: int = 0) -> Dict[str, Any]:
    """Allocate decode caches matching the trunk structure.

    With ``page_size`` the full-context attention layers allocate one
    block-paged pool of ``num_pages`` pages each (default: dense-equivalent
    capacity, ``batch * ceil(max_len / page_size)``) instead of a dense
    ``(batch, max_len)`` buffer; ``forward`` then needs ``block_tables``.
    Sliding-window rings, recurrent state, and MLA caches keep their dense
    per-slot layout (DESIGN.md §7.1).

    ``window_slack`` over-allocates sliding-window rings by that many
    positions beyond ``cfg.sliding_window`` (the attention *mask* still
    uses the config window). Speculative decoding needs it: a cursor
    rewind after a rejected draft must not have let the ring's write head
    lap a position that is still inside the mask window — with ``slack >=
    k`` draft writes land only on slots that are already outside the mask
    for every attendable query, so stale words are overwritten before they
    can ever be read (DESIGN.md §11).
    """
    prefix, n_periods, period = cfg.layer_pattern()
    if page_size is not None and num_pages is None:
        num_pages = batch * (-(-max_len // page_size))
    paged = page_size is not None and not cfg.use_mla

    def one(kind):
        if kind in ("dense", "global", "moe"):
            if cfg.use_mla:
                return attn_mod.init_mla_cache(batch, max_len, cfg)
            if paged:
                return attn_mod.init_paged_kv_cache(batch, num_pages,
                                                    page_size, cfg)
            return attn_mod.init_kv_cache(batch, max_len, cfg)
        if kind == "local":
            return attn_mod.init_kv_cache(
                batch, max_len, cfg,
                window=cfg.sliding_window + window_slack)
        if kind == "shared_attn":
            if paged:
                return attn_mod.init_paged_kv_cache(batch, num_pages,
                                                    page_size, cfg)
            return attn_mod.init_kv_cache(batch, max_len, cfg)
        if kind == "mamba":
            return ssm_mod.init_mamba_state(batch, cfg)
        if kind == "rwkv":
            return rwkv_mod.init_rwkv_state(batch, cfg)
        raise ValueError(kind)

    caches: Dict[str, Any] = {}
    if prefix:
        caches["prefix"] = [one(k) for k in prefix]
    if n_periods:
        stack = lambda tree: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(), tree)
        caches["period"] = {f"pos{i}": stack(one(k))
                            for i, k in enumerate(period)}
    return caches


def decode_step(params, caches, tokens, cfg: ArchConfig,
                qcfg: Optional[QuantConfig] = None, *,
                pos_offset, block_tables: Optional[jax.Array] = None,
                scan_unroll: int | bool = 1
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One incremental step (S small, typically 1). Returns (logits, caches)."""
    out = forward(params, tokens, cfg, qcfg, caches=caches,
                  pos_offset=pos_offset, block_tables=block_tables,
                  scan_unroll=scan_unroll)
    return out.logits[:, -1], out.caches
