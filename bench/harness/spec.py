"""Find a cell's configuration and traffic by the names in BENCHMARK.json.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``). The configuration file holds the
model's published sizes under ``model`` (Hugging Face ``config.json`` keys),
the program registry entry it runs (``arch``) and, per program entry
(``train``, ``serve``), that entry's options. Nothing here is specific to
one cell.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict

# Hugging Face config.json key -> the program's ArchConfig field
MODEL_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


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
    bench = benchmark or load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; one of {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, workload=w, benchmark=bench)


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry with every published size of the file applied over it."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    kw = {MODEL_KEYS[k]: v for k, v in config["model"].items()
          if k in MODEL_KEYS}
    return dataclasses.replace(base, **kw)
