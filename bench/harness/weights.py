"""The program's parameter tree, filled with the benchmark's seeded words.

The tree's structure and shapes come from the program's own initializer,
read abstractly (``jax.eval_shape``: no values); every value comes from
``lnsgen`` under the benchmark's seed. Matrices (>= 2-D per layer) become
packed ``LNSWeight`` leaves, norm gains stay float32, as the program keeps
them. One jitted call makes the whole tree on the device.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.harness import lnsgen

WEIGHT_STREAM = 1


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_std(path: str, shape: Tuple[int, ...]) -> float:
    """The program's init scale: 0.02 for the embedding table, 1/sqrt(fan
    in) for a projection."""
    if path.startswith("embed/"):
        return 0.02
    return 1.0 / math.sqrt(shape[-2])


def dense_shapes(cfg):
    from repro.models.model import init_params
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def make_params(words: jax.Array, cfg, bits: int, gamma: int):
    """The parameter tree for ``cfg`` with ``bits``/``gamma`` words."""
    from repro.core.lns import LNSFormat, LNSWeight
    fmt = LNSFormat(bits=bits, gamma=gamma)
    root = lnsgen.root_key(words, WEIGHT_STREAM)

    def leaf(path, sd):
        name = path_str(path)
        key = lnsgen.path_key(root, name)
        stacked = name.startswith("period/")
        shape = tuple(sd.shape[1:] if stacked else sd.shape)
        if len(shape) < 2:
            if stacked:
                return lnsgen.gain_stack(key, sd.shape[0], shape)
            return lnsgen.gain_values(key, shape)
        std = leaf_std(name, shape)
        if stacked:
            w, s = lnsgen.packed_stack(key, sd.shape[0], shape, std, bits,
                                       gamma)
        else:
            w, s = lnsgen.packed_slice(key, shape, std, bits, gamma)
        return LNSWeight(w, s, None, fmt)

    return jax.tree_util.tree_map_with_path(leaf, dense_shapes(cfg))


def make_train_state(words: jax.Array, cfg, mcfg):
    """``TrainState`` at step 0: seeded words on the update grid, zero
    second moments."""
    from repro.optim.madam import madam_lns
    from repro.training.steps import TrainState
    fmt = mcfg.update_format
    params = make_params(words, cfg, fmt.bits, fmt.gamma)
    init_opt, _ = madam_lns(mcfg)
    return TrainState(params=params, opt=init_opt(params),
                      step=jnp.zeros((), jnp.int32))
