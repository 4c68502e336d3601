"""Logical axis assignment for parameter / optimizer / cache pytrees.

Maps each leaf (by its tree path) to a tuple of logical axis names, then
resolves them against the active mesh + rules into NamedShardings. Stacked
(scanned) period parameters get a leading "stack" axis; LNSWeight leaves
shard the packed words like the dense weight and the scale with its size-1
axis unsharded.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from repro.core.lns import LNSWeight, is_lns_weight
from repro.distributed.sharding import logical_sharding, spec_for

__all__ = ["params_logical_axes", "params_shardings", "batch_shardings",
           "cache_logical_axes", "tree_shardings", "opt_logical_axes"]

# leaf-name (with optional parent context) -> logical axes of the 2D core
_BY_NAME: Dict[str, Tuple[Optional[str], ...]] = {
    "tok": ("vocab", "embed"),
    "head": ("embed", "vocab"),
    "wq": ("embed", "qkv_out"),
    "wk": ("embed", "qkv_out"),
    "wv": ("embed", "qkv_out"),
    "wo": ("qkv_out", "embed"),
    "bq": ("qkv_out",),
    "bk": ("qkv_out",),
    "bv": ("qkv_out",),
    "q_down": ("embed", None),
    "q_up": (None, "qkv_out"),
    "kv_down": ("embed", None),
    "kv_up": (None, "qkv_out"),
    "up": ("embed", "mlp"),
    "gate": ("embed", "mlp"),
    "down": ("mlp", "embed"),
    "router": ("embed", None),
    "w_up": ("experts", "embed", "moe_ff"),
    "w_gate": ("experts", "embed", "moe_ff"),
    "w_down": ("experts", "moe_ff", "embed"),
    "z_proj": ("embed", "ssm_inner"),
    "x_proj": ("embed", "ssm_inner"),
    "b_proj": ("embed", None),
    "c_proj": ("embed", None),
    "dt_proj": ("embed", None),
    "out_proj": ("ssm_inner", "embed"),
    "conv_wx": (None, "ssm_inner"),
    "norm": ("ssm_inner",),
    "wr": ("embed", "ssm_inner"),
    "wg": ("embed", "ssm_inner"),
    "ck": ("embed", "mlp"),
    "cv": ("mlp", "embed"),
    "cr": ("embed", None),
    "lora_a": ("embed", "lora"),
    "lora_b": ("lora", "qkv_out"),
    "proj": (None, "embed"),        # mtp combiner
}

# rwkv overrides (wk/wv/wo collide with attention names)
_RWKV_NAMES = {
    "wk": ("embed", "ssm_inner"),
    "wv": ("embed", "ssm_inner"),
    "wo": ("ssm_inner", "embed"),
}

# serving-forward overrides: the second GEMM of each column-parallel pair
# keeps its contraction dim replicated so the contraction never psums over a
# shard (psum reorders accumulation and breaks token-for-token equality with
# the single-device engine; the all-gather epilogue on the activation side is
# the serving rules' job — see ``sharding.serving_rules``).
_SERVING_NAMES = {
    "wo": (None, "embed"),
    "down": (None, "embed"),
    "out_proj": (None, "embed"),
    "cv": (None, "embed"),
}


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(f"[{k.idx}]")
        elif hasattr(k, "name"):
            out.append(str(k.name))
    return tuple(out)


def _leaf_axes(path_names: Tuple[str, ...], ndim: int,
               serving: bool = False) -> Tuple[Optional[str], ...]:
    name = path_names[-1] if path_names else ""
    in_rwkv = "rwkv" in path_names
    table = dict(_BY_NAME)
    if in_rwkv:
        table.update(_RWKV_NAMES)
    if serving:
        table.update(_SERVING_NAMES)
    axes = table.get(name)
    if axes is None:
        axes = (None,) * ndim  # norms / scalars / unknown -> replicated
    # stacked (scanned) leading axis
    if ndim > len(axes):
        axes = ("stack",) * (ndim - len(axes)) + tuple(axes)
    elif ndim < len(axes):
        axes = tuple(axes[-ndim:]) if ndim else ()
    return tuple(axes)


def params_logical_axes(params, serving: bool = False) -> Any:
    """Tree of logical-axes tuples matching ``params`` (LNSWeight-aware).

    ``serving=True`` applies the serving-forward per-leaf overrides (second
    GEMMs keep their contraction dim replicated — see ``_SERVING_NAMES``)."""

    def visit(path, leaf):
        names = _path_names(path)
        if is_lns_weight(leaf):
            axes = _leaf_axes(names, leaf.packed.ndim, serving)
            scale_axes = tuple(a if leaf.scale.shape[i] != 1 else None
                               for i, a in enumerate(axes)) \
                if leaf.scale.ndim == leaf.packed.ndim else (None,) * leaf.scale.ndim
            # keep the leaf's fmt aux so the axes/shardings tree structure
            # matches the params tree exactly (jit in_shardings prefix match)
            return LNSWeight(packed=axes, scale=scale_axes, fmt=leaf.fmt)
        return _leaf_axes(names, getattr(leaf, "ndim", 0), serving)

    return jax.tree_util.tree_map_with_path(visit, params,
                                            is_leaf=is_lns_weight)


def tree_shardings(axes_tree, mesh: Mesh, rules=None):
    """Resolve a logical-axes tree into NamedShardings."""
    def one(axes):
        return logical_sharding(axes, mesh, rules) or NamedSharding(
            mesh, spec_for((), mesh, rules))

    is_axes_leaf = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    return jax.tree.map(one, axes_tree, is_leaf=is_axes_leaf)


def params_shardings(params, mesh: Mesh, rules=None, serving: bool = False):
    return tree_shardings(params_logical_axes(params, serving), mesh, rules)


def batch_shardings(batch, mesh: Mesh, rules=None):
    """Batch tensors shard over ("pod","data") on the batch axis."""
    def one(leaf):
        axes = ("batch",) + (None,) * (leaf.ndim - 1)
        return logical_sharding(axes, mesh, rules)
    return jax.tree.map(one, batch)


def opt_logical_axes(params, opt_state):
    """Axes for a MadamState: g2 mirrors the weight (factored leaves get the
    row/col marginals of the weight's axes); count replicated."""
    p_axes = params_logical_axes(params)

    def leaf_axes(axes, g2_leaf):
        code_axes = axes.packed if isinstance(axes, LNSWeight) else axes
        if isinstance(g2_leaf, dict):  # factored {r, c}
            return {"r": tuple(code_axes[:-1]),
                    "c": tuple(code_axes[:-2]) + tuple(code_axes[-1:])}
        return tuple(code_axes)

    flat_axes, treedef = jax.tree_util.tree_flatten(
        p_axes, is_leaf=lambda x: isinstance(x, LNSWeight) or (
            isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                         for a in x)))
    flat_g2 = treedef.flatten_up_to(opt_state.g2)
    g2_axes = jax.tree_util.tree_unflatten(
        treedef, [leaf_axes(a, g) for a, g in zip(flat_axes, flat_g2)])
    return type(opt_state)(g2=g2_axes, count=())


# decode-cache leaves by name. k/v carry both "kv_seq" and "kv_heads": under
# the default (training) rules both map to "model" and spec_for's first-wins
# dedup keeps the split-KV layout; serving rules set kv_seq -> None so the
# same annotation becomes head-sharded (pools likewise, minus the batch dim).
_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "k_scale": ("batch", "kv_seq", "kv_heads", None),
    "v_scale": ("batch", "kv_seq", "kv_heads", None),
    "kp": (None, "kv_heads", None, None),
    "vp": (None, "kv_heads", None, None),
    "kp_scale": (None, "kv_heads", None, None),
    "vp_scale": (None, "kv_heads", None, None),
    "c_kv": ("batch", "kv_seq", None),
    "k_rope": ("batch", "kv_seq", None),
    "ssm": ("batch", "act_heads", None, None),
    "S": ("batch", "act_heads", None, None),
    "conv_x": ("batch", None, "ssm_inner"),
    "conv_b": ("batch", None, None),
    "conv_c": ("batch", None, None),
    "shift_tm": ("batch", None),
    "shift_cm": ("batch", None),
    "idx": ("batch",),  # per-slot length cursor rides with its cache rows
}


def cache_logical_axes(caches) -> Any:
    def visit(path, leaf):
        names = _path_names(path)
        axes = _CACHE_AXES.get(names[-1], (None,) * leaf.ndim)
        if leaf.ndim > len(axes):
            axes = ("stack",) * (leaf.ndim - len(axes)) + tuple(axes)
        return tuple(axes)
    return jax.tree_util.tree_map_with_path(visit, caches)
