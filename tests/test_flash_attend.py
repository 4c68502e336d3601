"""Fused flash-attention kernel (interpret mode) vs the jnp oracle:
output and dq/dk/dv, under remat inside a scan, and the dispatch rule."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import flash_attend as fa
from repro.models.attention import flash_attention

B, S, H = 1, 256, 2
TOL = dict(rtol=1e-4, atol=1e-4)


def _qkv(key, d=32, dv=32, s=S, sk=None):
    sk = s if sk is None else sk
    q = jax.random.normal(key, (B, s, H, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, sk, H, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, sk, H, dv))
    return q, k, v


def _kernel(blocks, window=None, softcap=None):
    """The kernel's custom_vjp at explicit blocks, in (B, S, H, D)."""
    def attend(q, k, v):
        hm = lambda x: x.transpose(0, 2, 1, 3)
        out = fa._attend(hm(q), hm(k), hm(v), 1.0 / math.sqrt(q.shape[-1]),
                         window, softcap, blocks, True)
        return hm(out)
    return attend


def _value_and_grads(attend, q, k, v, g):
    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) * g)
    out = attend(q, k, v)
    return (out,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("blocks", [(64, 64), (64, 128), (128, 64)],
                         ids=lambda b: f"bq{b[0]}-bk{b[1]}")
@pytest.mark.parametrize("window,softcap,dv", [
    (None, None, 32), (48, None, 32), (None, 30.0, 32), (None, None, 16),
    (130, 25.0, 32)], ids=["causal", "window", "softcap", "dv16",
                           "window-softcap"])
def test_kernel_matches_reference(key, blocks, window, softcap, dv):
    q, k, v = _qkv(key, dv=dv)
    g = jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, dv))
    got = _value_and_grads(_kernel(blocks, window, softcap), q, k, v, g)
    want = _value_and_grads(
        lambda q, k, v: flash_attention(q, k, v, window=window,
                                        softcap=softcap, block_k=64),
        q, k, v, g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL,
                                   err_msg=name)


def test_public_entry_matches_reference(key):
    """``flash_attend`` at the blocks its shape picks (one 256 block)."""
    q, k, v = _qkv(key)
    got = fa.flash_attend(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(flash_attention(q, k, v)), **TOL)


def test_grads_match_under_remat_in_scan(key):
    """As ``models/model.py`` runs it: a scan over layers whose body is
    ``jax.checkpoint(policy=nothing_saveable)``, so the forward kernel
    reruns inside the backward."""
    layers, d = 2, 32
    x0 = jax.random.normal(key, (B, S, H, d))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                       (layers, 3, H, d))

    def model(attend):
        def body(x, wl):
            return x + attend(x * wl[0], x * wl[1], x * wl[2]), None
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

        def loss(x0, w):
            x, _ = jax.lax.scan(body, x0, w)
            return jnp.sum(jnp.sin(x))
        return jax.grad(loss, argnums=(0, 1))(x0, w)

    got = model(_kernel((64, 128), window=96))
    want = model(lambda q, k, v: flash_attention(q, k, v, window=96,
                                                 block_k=64))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def _routes_to_kernel(q, k, v, **kw):
    def loss(q, k, v):
        return jnp.sum(dispatch.flash_attend(q, k, v, interpret=True, **kw))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("case,kernel", [
    ("tiles", True), ("reference-backend", False), ("seq-not-tiled", False),
    ("sq-ne-skv", False), ("head-dim-unsupported", False)])
def test_dispatch_routes_by_shape(key, case, kernel):
    shapes = {"tiles": {}, "reference-backend": {}, "seq-not-tiled":
              {"s": 200}, "sq-ne-skv": {"s": 128, "sk": 256},
              "head-dim-unsupported": {"d": 20, "dv": 20}}[case]
    q, k, v = _qkv(key, **shapes)
    backend = "reference" if case == "reference-backend" else "pallas"
    assert _routes_to_kernel(q, k, v, backend=backend) is kernel


def test_dispatch_fallback_equals_reference(key):
    """A shape left to the oracle gives the oracle's numbers."""
    q, k, v = _qkv(key, s=128, sk=256)
    got = dispatch.flash_attend(q, k, v, window=64, backend="pallas",
                                interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(flash_attention(q, k, v, window=64)))


@pytest.mark.parametrize("seq,blocks", [
    (2048, (512, 1024)), (1024, (512, 1024)), (384, (128, 128)),
    (768, (256, 256)), (512, (512, 512)), (64, (64, 64)), (200, None),
    (12, None)])
def test_block_sizes_from_shape(seq, blocks):
    assert fa.block_sizes(seq) == blocks
