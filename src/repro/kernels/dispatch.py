"""Kernel backend registry: one dispatch point for the LNS hot paths.

Every production consumer of the packed-LNS datapath (``qeinsum`` weight
GEMMs, the Madam update, activation encode) and of the attention kernels
(``paged_attend`` for serving, ``flash_attend`` for training) routes
through this module
instead of importing a kernel directly (DESIGN.md §4). Two backends:

* ``"pallas"``    — the Pallas TPU kernels (compiled Mosaic on real TPUs,
  interpret mode elsewhere). Default on TPU/GPU.
* ``"reference"`` — pure-jnp oracles with bit-identical semantics. Default
  on CPU, where interpret-mode Pallas is a ~100x slowdown; also the
  equivalence anchor the tests pin the kernels against.

Selection precedence (one rule for every op, highest first):

1. :func:`configure` / the :func:`configured` context manager — the
   process-level override an application sets once at startup.
2. The explicit per-call ``backend=`` / ``interpret=`` argument — this is
   the channel config fields (``QuantConfig.backend`` et al.) thread
   through, so a config field behaves as a per-call argument.
3. ``REPRO_KERNEL_BACKEND`` / ``REPRO_KERNEL_INTERPRET`` env vars — the
   ambient outermost layer (CI legs, one-off shell runs).
4. Platform auto-detection: pallas+compiled on TPU/GPU, reference (and
   interpret-mode Pallas where explicitly requested) elsewhere — compiled
   Mosaic is never silently replaced by the interpreter on hardware, and
   the interpreter is never accidentally shipped to a TPU job.

Env vars and :func:`configure` state are read at trace time — set them
before the first jit of a step function.

Kernel time is read from a profiler trace (DESIGN.md §13): each Pallas
kernel runs under its jitted ``*_pallas`` wrapper, whose name the
compiled program's custom call and the device trace's events carry.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from repro.core.lns import (LNSFormat, compute_scale, lns_decode_packed,
                            lns_encode, lns_pack, lns_requant_packed,
                            lns_unpack, lns_word_dtype)

__all__ = [
    "BACKENDS",
    "ENV_BACKEND",
    "ENV_INTERPRET",
    "configure",
    "configured",
    "get_configured",
    "default_backend",
    "resolve_backend",
    "resolve_interpret",
    "qmatmul",
    "encode_pack",
    "requant_pack",
    "madam_step",
    "paged_attend",
    "flash_attend",
    "fused_sample",
]

BACKENDS = ("pallas", "reference")
ENV_BACKEND = "REPRO_KERNEL_BACKEND"
ENV_INTERPRET = "REPRO_KERNEL_INTERPRET"

_UNSET = object()  # configure() sentinel: "leave this layer untouched"


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """The process-level override layer (precedence layer 1). ``None``
    fields fall through to the per-call argument / env / auto layers."""

    backend: Optional[str] = None
    interpret: Optional[bool] = None


_configured = DispatchConfig()


def configure(*, backend=_UNSET, interpret=_UNSET) -> DispatchConfig:
    """Set the process-level kernel dispatch override.

    ``configure(backend="reference")`` pins every dispatched op to the
    jnp oracle regardless of per-call arguments or env vars; ``None``
    clears a field back to the lower layers. Omitted fields are left
    untouched. Returns the new state. Applies at trace time — call it
    before the first jit of a step function.
    """
    global _configured
    kw = {}
    if backend is not _UNSET:
        if backend is not None and backend not in BACKENDS:
            raise ValueError(
                f"backend {backend!r}: expected one of {BACKENDS} or None")
        kw["backend"] = backend
    if interpret is not _UNSET:
        kw["interpret"] = None if interpret is None else bool(interpret)
    _configured = dataclasses.replace(_configured, **kw)
    return _configured


def get_configured() -> DispatchConfig:
    """The current process-level override state (read-only snapshot)."""
    return _configured


@contextlib.contextmanager
def configured(*, backend=_UNSET, interpret=_UNSET) -> Iterator[DispatchConfig]:
    """Scoped :func:`configure`: apply overrides inside a ``with`` block,
    restore the previous state on exit (exceptions included).

    >>> with dispatch.configured(backend="reference"):
    ...     engine.run(requests)   # every dispatched op hits the oracle
    """
    global _configured
    prev = _configured
    try:
        yield configure(backend=backend, interpret=interpret)
    finally:
        _configured = prev


def default_backend() -> str:
    """``REPRO_KERNEL_BACKEND`` if set, else pallas on TPU/GPU, reference
    elsewhere. (Layers 3-4 only — :func:`resolve_backend` adds the rest.)"""
    env = os.environ.get(ENV_BACKEND, "").strip().lower()
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"{ENV_BACKEND}={env!r}: expected one of {BACKENDS}")
        return env
    return "pallas" if jax.default_backend() in ("tpu", "gpu") else "reference"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Full precedence chain: configure() > per-call arg > env > auto."""
    if _configured.backend is not None:
        return _configured.backend
    if backend is None:
        return default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r}: expected one of {BACKENDS}")
    return backend


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret-mode resolution: configure() > per-call arg > env > auto.

    Auto-detection: compiled wherever the pallas backend is the default
    (TPU: Mosaic, GPU: Triton), interpreter elsewhere — so the platforms
    that default to ``"pallas"`` never silently run the ~100x interpreter.
    Env values: {auto, 0, 1, false, true}.
    """
    if _configured.interpret is not None:
        return _configured.interpret
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(ENV_INTERPRET, "auto").strip().lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    if env not in ("", "auto"):
        raise ValueError(
            f"{ENV_INTERPRET}={env!r}: expected auto, 0, 1, false or true")
    return jax.default_backend() not in ("tpu", "gpu")


def _on_mesh(fn, *args, in_specs=None, out_specs=None):
    """Call a Pallas kernel wrapper ``fn(*args)`` under the active mesh.

    XLA cannot partition a Mosaic kernel, so on a multi-device mesh the
    call runs inside ``shard_map``: operands enter with ``in_specs``
    (default: replicated, so every device computes the whole op exactly
    as one device would) and leave with ``out_specs`` (default:
    replicated). Without a mesh, or on a one-device mesh, ``fn`` is called
    directly.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=in_specs if in_specs is not None else (P(),) * len(args),
        out_specs=out_specs if out_specs is not None else P(),
        check_vma=False)(*args)


# ---------------------------------------------------------------------------
# dispatched operations


def qmatmul(pa: jax.Array, pb: jax.Array, fmt: LNSFormat,
            scale_a: Optional[jax.Array] = None,
            scale_b: Optional[jax.Array] = None, *,
            compute_dtype=jnp.bfloat16,
            backend: Optional[str] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """Packed ``pa (M,K) @ pb (K,N)`` -> f32, per-row/col scale epilogue."""
    if resolve_backend(backend) == "pallas":
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import model_axis_size
        from repro.kernels.ops import lns_qmatmul
        interp = resolve_interpret(interpret)
        # column-parallel where the mesh's model axis divides N: each device
        # owns N/m output columns; the kernel's K tiling is fixed, so every
        # column is bit-identical to the one-device result
        m = model_axis_size()
        col = "model" if m > 1 and pb.shape[1] % m == 0 else None
        per_col = getattr(scale_b, "ndim", 0) == 2 and scale_b.shape[1] > 1
        return _on_mesh(
            lambda a, b, sa, sb: lns_qmatmul(a, b, fmt, sa, sb,
                                             compute_dtype=compute_dtype,
                                             interpret=interp),
            pa, pb, scale_a, scale_b,
            in_specs=(P(), P(None, col), P(), P(None, col) if per_col else P()),
            out_specs=P(None, col))
    a = lns_decode_packed(pa, fmt, compute_dtype)
    b = lns_decode_packed(pb, fmt, compute_dtype)
    out = jnp.dot(a, b, preferred_element_type=jnp.float32)
    if scale_a is not None:
        out = out * scale_a
    if scale_b is not None:
        out = out * scale_b
    return out


def encode_pack(x: jax.Array, fmt: LNSFormat, scale_axis: Optional[int] = None,
                *, backend: Optional[str] = None,
                interpret: Optional[bool] = None):
    """Q_log-encode a 2-D tensor into packed words + its broadcast scale.

    Returns ``(packed (R,C), scale (R,1) f32)``; ``scale_axis=0`` keeps
    per-row scales, ``None`` is per-tensor (broadcast to (R,1)).
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels.ops import quantize_pack
        interp = resolve_interpret(interpret)
        return _on_mesh(lambda x: quantize_pack(x, fmt, scale_axis,
                                                interpret=interp), x)
    R = x.shape[0]
    scale = compute_scale(x, axis=scale_axis)
    srow = jnp.broadcast_to(
        scale.reshape(-1, 1) if scale.ndim else scale, (R, 1)
    ).astype(jnp.float32)
    sign, code = lns_encode(x, fmt, srow)
    return lns_pack(sign, code, fmt), srow


def requant_pack(packed: jax.Array, src: LNSFormat, dst: LNSFormat, *,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Re-grid packed wire words from ``src`` to ``dst`` bits (any rank).

    The self-speculative draft transform (paper §6.1.1): a lower-bitwidth
    *view* of the same weights on a coarser exponent grid — integer-only,
    sign preserved, scales untouched. Both backends are bit-identical: the
    Pallas kernel body traces :func:`lns_requant_packed` directly.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels.ops import requant_pack as requant_pack_op
        interp = resolve_interpret(interpret)
        return _on_mesh(lambda p: requant_pack_op(p, src, dst,
                                                  interpret=interp), packed)
    return lns_requant_packed(packed, src, dst)


def madam_step(packed: jax.Array, g: jax.Array, v: jax.Array,
               count: jax.Array, fmt: LNSFormat, *, lr: float,
               beta: float = 0.999, eps: float = 1e-30,
               with_stats: bool = False,
               requant_fmt: Optional[LNSFormat] = None,
               backend: Optional[str] = None,
               interpret: Optional[bool] = None):
    """Fused Algorithm-1 step on a packed >=2-D leaf. Returns
    ``(new_packed, new_v)``, or ``(new_packed, new_v, stats)`` with
    ``with_stats=True``.

    One HBM pass over (packed, grad, v): the second-moment EMA, the
    bias-corrected normalization, and the integer exponent step all happen
    on the word in VMEM — the sign bit is carried through untouched
    (multiplicative updates never flip sign). Leaves of any rank fold to
    2-D (the update is elementwise).

    ``with_stats`` folds the numerics-telemetry epilogue (DESIGN.md §14)
    into the same pass: ``stats`` is a dict of scalar traces keyed by
    ``MADAM_STAT_KEYS`` — rail saturation fractions, dead-update
    fraction, realized-vs-ideal step error, code mean, and (when
    ``requant_fmt`` names a coarser forward grid) the fraction of codes
    the B_U -> B_W re-grid will clamp.
    """
    from repro.kernels.madam_update import madam_stats_dict, requant_spec
    shape = packed.shape
    if packed.ndim < 2:
        raise ValueError(f"madam_step needs a >=2-D leaf, got {shape}")
    p2 = packed.reshape(-1, shape[-1])
    g2 = g.reshape(p2.shape)
    v2 = v.reshape(p2.shape)
    requant = requant_spec(fmt, requant_fmt) if with_stats else None
    vec = None
    if resolve_backend(backend) == "pallas":
        from repro.kernels.ops import madam_step_packed, madam_step_packed_stats
        interp = resolve_interpret(interpret)
        if with_stats:
            np_, nv, vec = _on_mesh(
                lambda p, g, v, c: madam_step_packed_stats(
                    p, g, v, c, fmt, lr=lr, beta=beta, eps=eps,
                    requant=requant, interpret=interp), p2, g2, v2, count)
        else:
            np_, nv = _on_mesh(
                lambda p, g, v, c: madam_step_packed(
                    p, g, v, c, fmt, lr=lr, beta=beta, eps=eps,
                    interpret=interp), p2, g2, v2, count)
    elif with_stats:
        np_, nv, vec = _madam_step_reference(p2, g2, v2, count, fmt, lr=lr,
                                             beta=beta, eps=eps,
                                             with_stats=True, requant=requant)
    else:
        np_, nv = _madam_step_reference(p2, g2, v2, count, fmt, lr=lr,
                                        beta=beta, eps=eps)
    if with_stats:
        stats = madam_stats_dict(vec, p2.size, fmt, requant_fmt)
        return np_.reshape(shape), nv.reshape(shape), stats
    return np_.reshape(shape), nv.reshape(shape)


def paged_attend(q: jax.Array, kp: jax.Array, vp: jax.Array,
                 k_scale: Optional[jax.Array], v_scale: Optional[jax.Array],
                 block_table: jax.Array, lengths: jax.Array, *,
                 fmt: Optional[LNSFormat] = None,
                 softcap: Optional[float] = None,
                 sm_scale: float,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Attend ``q`` over a block-paged KV pool through a block table.

    ``q`` is (B, S, H, hd); ``kp``/``vp`` are (P, KV, page, hd) pools —
    packed LNS words when ``fmt`` is given (with (P, KV, page, 1) scales),
    the compute dtype otherwise. ``block_table`` (B, max_pages) maps each
    slot's local page j to a pool page; ``lengths`` (B,) counts the valid
    positions per slot *including* the S just written, so query s sits at
    absolute position ``lengths - S + s``. Returns f32 (B, S, H, hd).

    The Pallas kernel serves decode (S == 1) *and* prefill-over-block-table
    (S > 1, the engine's batch-1 suffix prefill) shapes: pages gather
    tile-locally via scalar-prefetched block tables, double-buffered DMAs
    and in-kernel LNS decode (see ``kernels/paged_attend.py``). The
    reference backend is the jnp gather oracle below.

    Under an active mesh whose ``model`` axis divides the KV head count,
    either backend runs per-shard over its local head group via
    ``shard_map`` (pools head-sharded, one replicated logical block table)
    with an all-gather epilogue back to replicated heads — the collective
    placement lives here, in the dispatch layer, so the jnp reference and
    the Pallas kernel stay bit-comparable shard for shard.
    """
    use_pallas = resolve_backend(backend) == "pallas"
    interp = resolve_interpret(interpret) if use_pallas else None

    def impl(q, kp, vp, ks, vs, bt, ln):
        if use_pallas:
            from repro.kernels.ops import paged_attend_blocktable
            return paged_attend_blocktable(q, kp, vp, ks, vs, bt, ln,
                                           fmt=fmt, softcap=softcap,
                                           sm_scale=sm_scale,
                                           interpret=interp)
        return _paged_attend_reference(q, kp, vp, ks, vs, bt, ln, fmt=fmt,
                                       softcap=softcap, sm_scale=sm_scale)

    from repro.distributed.sharding import model_axis_size
    args = (q, kp, vp, k_scale, v_scale, block_table, lengths)
    m = model_axis_size()
    if m > 1 and kp.shape[1] % m == 0:
        # head-group parallel over the mesh ``model`` axis: each shard
        # attends its local KV head group (and the matching query group —
        # GQA groups are contiguous in the head axis, so an even split
        # never severs one) against its slice of every pool page; the
        # replicated block table and lengths give every shard the same
        # page-local view of the one logical table. Heads never mix across
        # shards, so each shard's result is bitwise what one device
        # computes for those heads; the trailing constraint is the
        # explicit all-gather epilogue back to replicated heads.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.sharding import current_mesh
        heads = P(None, None, "model", None)        # q / out: (B, S, h, hd)
        pool = P(None, "model", None, None)         # (P, kv, page, ·)
        out = _on_mesh(impl, *args, out_specs=heads,
                       in_specs=(heads, pool, pool, pool, pool, P(), P()))
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(current_mesh(), P()))
    return _on_mesh(impl, *args) if use_pallas else impl(*args)


def flash_attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 scale: Optional[float] = None,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Causal (optionally windowed, soft-capped) attention for training
    and dense prefill: ``q (B, S, H, D)`` over ``k (B, Skv, H, D)``,
    ``v (B, Skv, H, Dv)`` with KV heads already repeated -> ``(B, S, H,
    Dv)`` in q's dtype.

    The fused Pallas kernel (``kernels/flash_attend.py``, forward and
    backward) runs where the backend resolves to ``"pallas"``, ``S ==
    Skv``, the kernel tiles the shape (``flash_attend.supported``) and the
    heads are whole on each device or sharded over the mesh's ``model``
    axis (each shard attends its own heads). Everything else — the
    sequence-parallel layout for head counts the model axis does not
    divide, shapes the kernel does not tile — takes the jnp oracle,
    ``models.attention.flash_attention``.
    """
    from repro.distributed.sharding import model_axis_size, spec_for
    from repro.kernels import flash_attend as fa
    from repro.models.attention import flash_attention
    S, H, D = q.shape[1], q.shape[2], q.shape[3]
    if (resolve_backend(backend) == "pallas" and k.shape[1] == S
            and fa.supported(S, D, v.shape[-1])
            and H % model_axis_size() == 0):
        interp = resolve_interpret(interpret)
        heads = spec_for(("batch", "seq", "act_heads", None))
        return _on_mesh(
            lambda q, k, v: fa.flash_attend(q, k, v, scale=scale,
                                            window=window, softcap=softcap,
                                            interpret=interp),
            q, k, v, in_specs=(heads,) * 3, out_specs=heads)
    return flash_attention(q, k, v, window=window, softcap=softcap,
                           scale=scale)


def fused_sample(logits: jax.Array, gumbel: Optional[jax.Array],
                 temp: Optional[jax.Array], *,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Token selection epilogue: ``logits (B, V)`` -> ``(B,)`` int32.

    ``gumbel is None`` is pure greedy (first-max-wins argmax over the raw
    logits). Otherwise each row draws ``argmax(logits / max(temp, 1e-6)
    + gumbel)`` when its ``temp > 0`` and falls back to greedy when not —
    exactly the sort-free fast path of ``server.sampling``. The gumbel
    noise is generated by the caller with ``jax.random`` (keys fold in the
    request seed/step), so a seeded request replays token-for-token on
    either backend; the kernel fuses only the scale/add/argmax epilogue.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels.ops import fused_sample as fused_sample_op
        interp = resolve_interpret(interpret)
        return _on_mesh(lambda lg, gm, t: fused_sample_op(lg, gm, t,
                                                          interpret=interp),
                        logits, gumbel, temp)
    return _fused_sample_reference(logits, gumbel, temp)


def _fused_sample_reference(logits, gumbel, temp):
    """jnp oracle for the fused sampler epilogue (first-max-wins argmax,
    bit-identical to the host-side np.argmax the engine once used)."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    if gumbel is None:
        return greedy
    scaled = lg / jnp.maximum(temp, 1e-6)[:, None]
    toks = jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, toks, greedy)


def _paged_attend_reference(q, kp, vp, k_scale, v_scale, block_table,
                            lengths, *, fmt, softcap, sm_scale):
    """jnp oracle: gather the slot's pages, decode, masked softmax.

    GQA is grouped rather than materialized: q reshapes to
    ``(B, S, kv, rep, hd)`` and the einsums carry the (group, repeat)
    axes, so the gathered KV view is never ``jnp.repeat``-ed ``rep``-fold
    — head ``h`` maps to group ``h // rep``, matching repeat semantics.
    """
    B, S, h, hd = q.shape
    kv, page = kp.shape[1], kp.shape[2]
    mp = block_table.shape[1]
    cap = mp * page

    def gather(pool):  # (B, mp, kv, page, ·) -> (B, cap, kv, ·)
        x = pool[block_table].transpose(0, 1, 3, 2, 4)
        return x.reshape(B, cap, kv, x.shape[-1])

    def view(pool, scale):
        x = gather(pool)
        if fmt is None:
            return x.astype(jnp.float32)
        s = gather(scale)
        return lns_decode_packed(x, fmt, jnp.float32) * s.astype(jnp.float32)

    rep = h // kv
    kf = view(kp, k_scale)                              # (B, cap, kv, hd)
    vf = view(vp, v_scale)
    qg = q.astype(jnp.float32).reshape(B, S, kv, rep, hd)
    logits = jnp.einsum("bsgrd,bkgd->bgrsk", qg, kf)    # (B, kv, rep, S, cap)
    logits = logits.reshape(B, h, S, cap) * sm_scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    abs_pos = jnp.arange(cap)
    q_pos = (lengths - S)[:, None] + jnp.arange(S)  # (B, S)
    mask = abs_pos[None, None, :] <= q_pos[:, :, None]
    logits = jnp.where(mask[:, None], logits, -1e30)
    p_attn = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bgrsk,bkgd->bsgrd",
                     p_attn.reshape(B, kv, rep, S, cap), vf)
    return ctx.reshape(B, S, h, hd)


def _madam_step_reference(packed, g, v, count, fmt: LNSFormat, *, lr, beta,
                          eps, with_stats=False, requant=None):
    """jnp oracle for the fused packed update — bit-exact to the kernel
    because both call the one shared ``_step_math`` tile function (and,
    with ``with_stats``, the one shared ``madam_stats_vec`` epilogue)."""
    from repro.kernels.madam_update import _step_math  # cycle-free lazy
    sign_bit = ((packed.astype(jnp.int32) >> (fmt.bits - 1)) & 1)
    _, code = lns_unpack(packed, fmt)
    bc = 1.0 - beta ** count.astype(jnp.float32)
    new_code, nv, target = _step_math(code, 1 - 2 * sign_bit, g, v, bc, lr=lr,
                                      beta=beta, eps=eps, gamma=fmt.gamma,
                                      max_code=fmt.max_code)
    word = (sign_bit << (fmt.bits - 1)) | new_code.astype(jnp.int32)
    word = word.astype(lns_word_dtype(fmt))
    if not with_stats:
        return word, nv
    from repro.kernels.madam_update import madam_stats_vec
    vec = madam_stats_vec(code, target, new_code, gamma=fmt.gamma,
                          max_code=fmt.max_code, requant=requant)
    return word, nv, vec
