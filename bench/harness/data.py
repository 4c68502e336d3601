"""Training tokens made on the device from the seed.

The same Markov-like stream as the program's synthetic corpus reader:
each row starts at a random token and moves by ``7 * k`` (mod vocab) with
``k`` drawn uniformly from ``[0, noise)``, so a model can learn it and
every row of every step differs. A batch is a pure function of the seed
words and the step index; nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import lnsgen

DATA_STREAM = 2


def make_batch(words: jax.Array, index: jax.Array, *, batch: int, seq: int,
               vocab: int, noise: int = 16):
    """``{"tokens", "labels"}``, each ``(batch, seq)`` int32."""
    key = jax.random.fold_in(lnsgen.root_key(words, DATA_STREAM), index)
    k1, k2 = jax.random.split(key)
    start = jax.random.randint(k1, (batch, 1), 0, vocab, jnp.int32)
    steps = jax.random.randint(k2, (batch, seq), 0, noise, jnp.int32)
    rest = (start + jnp.cumsum(steps, axis=1) * 7) % vocab
    full = jnp.concatenate([start, rest], axis=1)
    return {"tokens": full[:, :-1], "labels": full[:, 1:]}
