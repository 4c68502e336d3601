"""Production meshes.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

__all__ = ["EXACT_ROUNDING_FLAG", "make_mesh", "make_production_mesh",
           "make_host_mesh", "require_exact_rounding"]

# XLA:TPU may keep f32 precision across the bf16 ops that one fusion spans
# (excess precision). A mesh program's collectives cut fusions in other
# places than the one-device program's, so the two would round at
# different points and their token streams part at the first near-tie.
EXACT_ROUNDING_FLAG = "--xla_allow_excess_precision=false"


def require_exact_rounding() -> None:
    """Round every bf16 op to bf16 wherever fusion puts it, so a
    mesh-sharded serving program computes bit for bit what the one-device
    program does (DESIGN.md §12). XLA reads its flags when JAX first
    initializes a backend: call this before that, in an entry point's
    ``main()``; afterwards it has no effect."""
    flags = os.environ.get("XLA_FLAGS", "")
    if EXACT_ROUNDING_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {EXACT_ROUNDING_FLAG}".strip()


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding model
    ``distributed/sharding.py`` is written for (logical-rule
    ``with_sharding_constraint`` hints the partitioner completes). Newer
    JAX defaults to ``Explicit`` axes, which reject those constraints."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod adds an outer
    2-pod data-parallel axis (2x16x16 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests / smoke runs).

    Raises when the requested ``(data, model)`` shape asks for more devices
    than the platform exposes — a mesh test that silently collapsed to
    ``(n, 1)`` would pass vacuously on one device, which is exactly what CI
    mesh legs must not do. Start with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fake devices.
    """
    n = jax.device_count()
    if data * model > n:
        raise ValueError(
            f"make_host_mesh: requested mesh (data={data}, model={model}) "
            f"needs {data * model} devices but only {n} are available; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{data * model} (before importing jax) or shrink the mesh")
    return make_mesh((data, model), ("data", "model"))
