"""Step builders: LNS-native train step, prefill step, decode step.

The train step is the paper's full pipeline (Fig. 3), with the weights
never leaving the packed wire format (DESIGN.md §3-4):

  1. params stay packed LNS words end to end — routed GEMMs decode
     tile-locally inside the kernel, fallback leaves decode per leaf
     inside the scan body; there is NO whole-tree materialize and no
     fp master copy anywhere
  2. forward/backward with Q_A/Q_E quantization; gradients are taken
     w.r.t. zero delta carriers (``grad_proxies``) whose cotangent is
     exactly dL/dW at W = decode(packed)
  3. Q_G on the final weight gradients
  4. fused Madam update directly on the packed exponent words (one HBM
     pass per leaf through ``kernels/dispatch``)

Gradient microbatching (``accum_steps``) accumulates quantized microbatch
gradients — XLA overlaps each microbatch's backward with the previous
all-reduce (latency-hiding scheduler flags set in ``launch.train``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.lns import LNSFormat
from repro.core.quantizer import QuantConfig, quantize_grads
from repro.models.common import ArchConfig
from repro.models.model import decode_step as model_decode_step
from repro.models.model import forward, lm_loss
from repro.obs.numerics import grad_encode_stats
from repro.optim.madam import (MadamConfig, MadamState, attach_proxies,
                               grad_proxies, init_lns_params, madam_lns)

__all__ = ["TrainState", "init_train_state", "build_train_step",
           "build_prefill_step", "build_decode_step"]


class TrainState(NamedTuple):
    params: Any          # mixed LNSWeight / fp pytree
    opt: MadamState
    step: jax.Array


def init_train_state(key, cfg: ArchConfig, mcfg: MadamConfig) -> TrainState:
    """Initialize params directly in LNS (jit/eval_shape friendly)."""
    from repro.models.model import init_params
    dense = init_params(key, cfg)
    params = init_lns_params(dense, mcfg, scale_axis="auto")
    init_opt, _ = madam_lns(mcfg)
    return TrainState(params=params, opt=init_opt(params),
                      step=jnp.zeros((), jnp.int32))


def build_train_step(
    cfg: ArchConfig,
    qcfg: Optional[QuantConfig],
    mcfg: MadamConfig,
    *,
    accum_steps: int = 1,
    remat: bool = True,
    scan_unroll: int | bool = 1,
    numerics: bool = False,
) -> Callable:
    """Returns ``train_step(state, batch) -> (state, metrics)``.

    ``numerics=True`` adds a ``metrics["numerics"]`` aux pytree of
    per-layer LNS health scalars (DESIGN.md §14): update-site stats ride
    the fused Madam kernel's epilogue, encode-site rail stats fuse into
    the gradient quantizer's pass — all in-graph, one host sync per step
    (the loss the loop already blocks on).
    """
    _, opt_update = madam_lns(mcfg)
    # the forward re-grid target for the requant clip stat: the B_U-grid
    # weights are re-gridded to the (coarser) B_W forward format each GEMM
    fwd_fmt = getattr(qcfg, "weight", None) if numerics else None
    if not isinstance(fwd_fmt, LNSFormat):
        fwd_fmt = None

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        params = state.params  # packed LNSWeight / fp leaves, never dense

        def loss_fn(diff, mb):
            # diff: fp leaves + zero delta carriers for the packed leaves;
            # dL/ddelta == dL/dW — no dense master copy is differentiated
            return lm_loss(attach_proxies(params, diff), mb, cfg, qcfg,
                           remat=remat, scan_unroll=scan_unroll)

        def one_microbatch(diff, mb):
            loss, grads = jax.value_and_grad(loss_fn)(diff, mb)
            # encode-site stats read the RAW gradients — the same tensors
            # quantize_grads is about to push through the LNS grid, so XLA
            # CSEs the scale/log2 work with the encode itself (measuring
            # the quantized output instead would double the reductions and
            # see an already-clamped tensor)
            with jax.named_scope("grad_quantize"):
                enc = grad_encode_stats(grads, qcfg) if numerics else {}
                return loss, quantize_grads(grads, qcfg), enc

        # zeros fold to a broadcast constant inside jit: the carriers cost
        # no HBM; only the gradient outputs are dense
        diff0 = grad_proxies(params, cfg.compute_dtype)

        if accum_steps == 1:
            loss, grads, enc_stats = one_microbatch(diff0, batch)
        else:
            def split(x):
                return x.reshape((accum_steps, x.shape[0] // accum_steps)
                                 + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def body(carry, mb):
                loss_acc, g_acc = carry
                loss, g, enc = one_microbatch(diff0, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                # enc rides as a scan output: stacked per microbatch,
                # averaged below (no zero-init tree needed)
                return (loss_acc + loss, g_acc), enc

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), diff0)
            (loss, grads), enc_stack = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), mbs)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            enc_stats = jax.tree.map(lambda x: jnp.mean(x, axis=0),
                                     enc_stack)

        with jax.named_scope("optimizer"):
            if numerics:
                new_params, new_opt, upd_stats = opt_update(
                    grads, state.opt, state.params, with_stats=True,
                    requant_fmt=fwd_fmt)
            else:
                new_params, new_opt = opt_update(grads, state.opt,
                                                 state.params)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": state.step.astype(jnp.float32)}
        if numerics:
            metrics["numerics"] = {
                "update": upd_stats,
                "grad_encode": enc_stats,
            }
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def build_prefill_step(cfg: ArchConfig, qcfg: Optional[QuantConfig],
                       mcfg: Optional[MadamConfig] = None, *,
                       scan_unroll: int | bool = 1) -> Callable:
    """``prefill(params, batch) -> last-position logits``.

    Consumes packed ``LNSWeight`` leaves directly — routed GEMMs through
    ``kernels/dispatch``, per-leaf decode otherwise; there is no up-front
    materialize (``mcfg`` is accepted for signature compatibility). Runs
    the flash (training) attention path over the full prompt; the KV
    write-back is modeled by the decode cache in serving proper — its bytes
    are negligible next to prefill compute (DESIGN.md §Deviations).
    """
    del mcfg  # packed params are consumed as-is

    def prefill_step(params, batch):
        out = forward(params, batch["tokens"], cfg, qcfg,
                      patches=batch.get("patches"), remat=False,
                      scan_unroll=scan_unroll)
        return out.logits[:, -1]

    return prefill_step


def build_decode_step(cfg: ArchConfig, qcfg: Optional[QuantConfig],
                      mcfg: Optional[MadamConfig] = None, *,
                      scan_unroll: int | bool = 1) -> Callable:
    """``decode(params, caches, batch, pos) -> (logits, caches)``.

    Packed params are consumed as-is (see :func:`build_prefill_step`).
    """
    del mcfg

    def serve_step(params, caches, batch, pos):
        return model_decode_step(params, caches, batch["tokens"], cfg, qcfg,
                                 pos_offset=pos,
                                 block_tables=batch.get("block_tables"),
                                 scan_unroll=scan_unroll)

    return serve_step
