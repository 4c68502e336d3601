"""Seeded weights in the packed LNS wire format, made on the device.

The benchmark, not the program, makes every weight: a value ``z`` is drawn
from a truncated normal, and stored as the word ``sign << (bits-1) | code``
with ``code = clip(floor(-log2(|z| / s) * gamma + 0.5), 0, 2^(bits-1) - 1)``
and a power-of-two scale ``s`` per output column (the absmax over the
contraction axis, rounded up to a power of two). The program under test
and the plain reference both start from these words; nothing here imports
the program.

Keys: the run's seed (any non-negative integer below 2^64) folds into a
root key as two 32-bit words, so a new seed never recompiles anything. Each
leaf takes ``fold_in(root, crc32(path))`` and each layer slice of a stacked
leaf ``fold_in(leaf_key, layer)``, so the reference can remake one layer
at a time.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed as two uint32 words (low, high): the traced key material."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2^64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def root_key(words: jax.Array, stream: int) -> jax.Array:
    """Root key of one stream (weights, data, traffic) for the seed words."""
    k = jax.random.PRNGKey(stream)
    k = jax.random.fold_in(k, words[0])
    return jax.random.fold_in(k, words[1])


def path_key(root: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def word_dtype(bits: int):
    return jnp.uint8 if bits <= 8 else jnp.uint16


def lns_words(z: jax.Array, bits: int, gamma: int):
    """Encode a (K, N) float tensor: words (K, N) and scale (1, N)."""
    max_code = (1 << (bits - 1)) - 1
    zf = z.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(zf), axis=0, keepdims=True),
                       jnp.finfo(jnp.float32).tiny)
    scale = jnp.exp2(jnp.ceil(jnp.log2(amax)))
    mag = jnp.maximum(jnp.abs(zf) / scale, jnp.finfo(jnp.float32).tiny)
    code = jnp.clip(jnp.floor(-jnp.log2(mag) * gamma + 0.5), 0, max_code)
    word = ((zf < 0).astype(jnp.uint32) << (bits - 1)) | code.astype(jnp.uint32)
    return word.astype(word_dtype(bits)), scale


def decode_words(word: jax.Array, scale: jax.Array, bits: int, gamma: int,
                 dtype=jnp.float32) -> jax.Array:
    """``±scale * 2^(-code/gamma)`` in ``dtype``."""
    max_code = (1 << (bits - 1)) - 1
    w = word.astype(jnp.int32)
    code = (w & max_code).astype(jnp.float32)
    sign = 1.0 - 2.0 * ((w >> (bits - 1)) & 1).astype(jnp.float32)
    return (sign * jnp.exp2(-code / gamma) * scale.astype(jnp.float32)
            ).astype(dtype)


def regrid_words(word: jax.Array, src: tuple, dst: tuple) -> jax.Array:
    """Move words from ``(bits, gamma)`` ``src`` to a coarser or finer grid
    at the same scale, rounding half away from zero."""
    (sb, sg), (db, dg) = src, dst
    w = word.astype(jnp.int32)
    sign = (w >> (sb - 1)) & 1
    code = w & ((1 << (sb - 1)) - 1)
    if dg >= sg:
        code = code * (dg // sg)
    else:
        r = sg // dg
        code = (code + r // 2) // r
    code = jnp.clip(code, 0, (1 << (db - 1)) - 1)
    return ((sign << (db - 1)) | code).astype(word_dtype(db))


def slice_values(key: jax.Array, shape: tuple, std: float) -> jax.Array:
    """One matrix slice: ``std`` times a normal truncated at +-2."""
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def gain_values(key: jax.Array, shape: tuple) -> jax.Array:
    """A norm gain (the model applies ``1 + gain``): small, seeded."""
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def packed_slice(key: jax.Array, shape: tuple, std: float, bits: int,
                 gamma: int):
    return lns_words(slice_values(key, shape, std), bits, gamma)


def packed_stack(key: jax.Array, n: int, shape: tuple, std: float,
                 bits: int, gamma: int):
    """``n`` layer slices, one at a time on the device (a layer's float
    temporaries are the largest buffer held)."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.lax.map(
        lambda k: packed_slice(k, shape, std, bits, gamma), keys)


def gain_stack(key: jax.Array, n: int, shape: tuple) -> jax.Array:
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.lax.map(lambda k: gain_values(k, shape), keys)
