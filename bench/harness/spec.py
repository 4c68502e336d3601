"""Find a cell's configuration and traffic by the names in BENCHMARK.json.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``). The configuration file holds the
model's published sizes under ``model`` (Hugging Face ``config.json`` keys),
the program registry entry it runs (``arch``), per program entry
(``train``, ``serve``) that entry's options, and optionally:

* ``reference``: the file under ``bench/harness/`` of the architecture
  module that speaks for it (interface in ``reference.py``'s docstring);
  absent, the dense module ``bench.harness.reference``;
* ``mesh``: ``{"data": a, "model": b}`` with ``a * b`` the cell's chips;
  absent, a one-chip cell runs as it always has, without one.

Everything architecture-specific (which model keys reach the program, the
reference, its dims and the model FLOPs) comes from that module, resolved
here; nothing here is specific to one cell.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import re
import sys
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

DEFAULT_REFERENCE = "reference.py"
MODULE_FILE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\.py")
MESH_AXES = ("data", "model")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    workload: Dict[str, Any]
    benchmark: Dict[str, Any]

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    @property
    def arch(self):
        """The configuration's architecture module."""
        return reference_of(self.config)

    @property
    def dims(self):
        """The module's ``Dims`` of the configuration's model."""
        return self.arch.Dims.from_model(self.model)

    def make_mesh(self, devices):
        """The cell's ``(data, model)`` mesh over its first ``chips``
        devices, or None for a one-chip cell that names no mesh."""
        shape = mesh_shape(self.config, self.chips)
        if shape is None:
            return None
        import jax
        from jax.sharding import AxisType
        # Auto axes: the program's sharding rules are constraint hints
        # (launch/mesh.make_mesh builds its meshes the same way)
        return jax.make_mesh(shape, MESH_AXES,
                             devices=list(devices)[:self.chips],
                             axis_types=(AxisType.Auto,) * len(MESH_AXES))

    def metrics(self, group: str):
        """The cell's metrics of ``group`` (``end_to_end`` or
        ``per_layer``): those whose ``workloads`` list names it, or that
        have none."""
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str, benchmark: Dict[str, Any] = None
              ) -> Cell:
    """The cell, its configuration checked: every model key applied or
    recorded by its module, and the mesh matching its chips."""
    bench = benchmark or load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; one of {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, workload=w, benchmark=bench)
    model_fields(config)
    mesh_shape(config, cell.chips)
    return cell


def reference_of(config: Dict[str, Any]):
    """The architecture module the configuration names."""
    name = config.get("reference", DEFAULT_REFERENCE)
    if not MODULE_FILE.fullmatch(name):
        raise ValueError(f"configuration {config.get('name')!r}: reference "
                         f"{name!r} is not a file name under bench/harness/")
    try:
        return importlib.import_module(f"bench.harness.{name[:-3]}")
    except ModuleNotFoundError as e:
        raise ValueError(f"configuration {config.get('name')!r}: reference "
                         f"{name!r}: no such module under bench/harness/"
                         ) from e


def module_of(dims):
    """The architecture module whose ``Dims`` made ``dims``."""
    return sys.modules[type(dims).__module__]


def model_fields(config: Dict[str, Any]) -> Dict[str, Any]:
    """``{ArchConfig field: value}`` of the file's model keys. Raises,
    naming the key, where the module does not compute the model (its
    ``Dims.from_model`` refuses) or neither maps nor records a key."""
    arch = reference_of(config)
    model = config["model"]
    arch.Dims.from_model(model)
    unknown = sorted(k for k in model
                     if k not in arch.MODEL_KEYS and k not in arch.NOT_APPLIED)
    if unknown:
        raise ValueError(
            f"configuration {config.get('name')!r}: model key(s) "
            f"{', '.join(unknown)} neither applied nor recorded by "
            f"{arch.__name__} (its MODEL_KEYS and NOT_APPLIED)")
    return {arch.MODEL_KEYS[k]: v for k, v in model.items()
            if k in arch.MODEL_KEYS}


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry with every model key the module maps applied over it, each
    field then checked against the file."""
    from repro.configs import get_config
    keys = reference_of(config).MODEL_KEYS
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **model_fields(config))
    wrong = [f"{k} ({keys[k]} = {getattr(cfg, keys[k])!r}, the file says "
             f"{v!r})" for k, v in config["model"].items()
             if k in keys and getattr(cfg, keys[k]) != v]
    if wrong:
        raise ValueError(f"configuration {config.get('name')!r}: "
                         f"{'; '.join(wrong)}")
    return cfg


def mesh_shape(config: Dict[str, Any], chips: int
               ) -> Optional[Tuple[int, int]]:
    """``(data, model)`` of the configuration's mesh; None where it names
    none and the cell takes one chip."""
    mesh = config.get("mesh")
    if mesh is None:
        if chips != 1:
            raise ValueError(f"configuration {config.get('name')!r}: a cell "
                             f"on {chips} chips needs a mesh "
                             f"{{\"data\": a, \"model\": b}}")
        return None
    if set(mesh) != set(MESH_AXES):
        raise ValueError(f"configuration {config.get('name')!r}: mesh "
                         f"{mesh!r} must name exactly {MESH_AXES}")
    shape = tuple(int(mesh[a]) for a in MESH_AXES)
    if shape[0] * shape[1] != chips:
        raise ValueError(f"configuration {config.get('name')!r}: mesh "
                         f"{mesh!r} spans {shape[0] * shape[1]} chips, the "
                         f"cell takes {chips}")
    return shape
