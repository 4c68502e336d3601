"""Gradient compression: LNS-compressed all-reduce, error feedback,
signSGD majority vote (beyond-paper distributed feature)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.lns import LNSFormat
from repro.launch.mesh import make_mesh
from repro.optim.compression import (error_feedback_update,
                                     lns_compressed_psum, sign_majority_psum)

FMT = LNSFormat(bits=8, gamma=8)


def test_error_feedback_reduces_bias(key):
    """With error feedback, the running sum of quantized grads tracks the
    running sum of true grads (compression error doesn't accumulate)."""
    g = jax.random.normal(key, (64,)) * 0.3
    residual = jnp.zeros((64,))
    acc_q = jnp.zeros((64,))
    for i in range(50):
        q, residual = error_feedback_update({"g": g}, {"g": residual}, FMT)
        q, residual = q["g"], residual["g"]
        acc_q = acc_q + q
    acc_true = 50 * g
    rel = float(jnp.linalg.norm(acc_q - acc_true) / jnp.linalg.norm(acc_true))
    assert rel < 0.02  # unbiased up to the last step's residual


def test_plain_quantize_accumulates_bias(key):
    """Without feedback the same loop drifts more — why EF matters."""
    from repro.core.lns import lns_quantize
    g = jax.random.normal(key, (64,)) * 0.3
    acc_q = jnp.zeros((64,))
    for _ in range(50):
        acc_q = acc_q + lns_quantize(g, FMT)
    rel_nofb = float(jnp.linalg.norm(acc_q - 50 * g) / jnp.linalg.norm(50 * g))
    assert rel_nofb > 0.002  # deterministic rounding bias accumulates


def test_lns_compressed_psum_single_device(key):
    mesh = make_mesh((1,), ("data",))
    grads = {"w": jax.random.normal(key, (16,))}

    def f(g):
        out, _ = lns_compressed_psum(g, "data", FMT)
        return out

    out = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P())(grads)
    # single participant: psum of the quantized grad == quantized grad
    from repro.core.lns import lns_quantize
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(lns_quantize(grads["w"], FMT)),
                               rtol=1e-6)


def test_sign_majority_psum_single_device(key):
    mesh = make_mesh((1,), ("data",))
    grads = {"w": jax.random.normal(key, (16,))}

    def f(g):
        return sign_majority_psum(g, "data")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P())(grads)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.sign(np.asarray(grads["w"])))


def test_compressed_wire_bytes(key):
    """The wire format is 1 byte/element + one f32 scale: a 4x cut vs f32."""
    from repro.core.lns import compute_scale, lns_encode, lns_pack
    g = jax.random.normal(key, (1024,))
    s = compute_scale(g)
    sign, code = lns_encode(g, FMT, s)
    packed = lns_pack(sign, code, FMT)
    wire = packed.size * packed.dtype.itemsize + 4
    assert wire <= g.size * 4 / 3.9
