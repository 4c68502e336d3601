"""The harness driven end to end on the CPU at a tiny size, with the real
BENCHMARK.json, metric readers and limits."""
import argparse
import json
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

# smollm-135m's widths (so logits and gradients sit at the scale the
# limits were set at), two layers and a 2048-token vocabulary
TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 576,
              "num_attention_heads": 9, "num_key_value_heads": 3,
              "head_dim": 64, "intermediate_size": 1536, "vocab_size": 2048,
              "tie_word_embeddings": True, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-5}
TINY_ENGINE = {"num_slots": 4, "max_len": 128, "page_size": 16,
               "num_pages": 40, "alloc_policy": "ondemand",
               "prefix_cache": True}
TINY_TRAFFIC = {
    "train": {"kind": "train", "seq": 32, "batch": 4},
    "serve": {"kind": "serve", "rate": 20.0,
              "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 64},
              "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 32},
              "greedy_share": 0.5, "lead_in_s": 0.5},
}
TRAIN_CELL = "smollm-135m.train.seq2048"
SERVE_CELL = "smollm-135m.serve.short"


def tiny_cell(workload: str, chips: int = 1, **config):
    """The workload of BENCHMARK.json with its configuration cut to a tiny
    model and its traffic to a tiny mix (every other setting as is);
    ``chips`` and the configuration keys in ``config`` (``reference``,
    ``mesh``) replace the cell's."""
    from bench.harness import spec
    cell = spec.load_cell(ROOT, workload)
    cell.config = json.loads(json.dumps(cell.config))
    cell.config["model"] = dict(TINY_MODEL)
    if "serve" in cell.config:
        cell.config["serve"]["engine"] = dict(TINY_ENGINE)
    cell.config.update(config)
    cell.chips = chips
    cell.traffic = dict(TINY_TRAFFIC[cell.traffic["kind"]])
    return cell


def run_tiny(workload: str, seed: int = 2 ** 33 + 17, seconds: float = 1.5,
             trace: int = 0, cell=None, **run_kw):
    """One run of the harness on the CPU (of ``cell``, by default the tiny
    cell of ``workload``), its look for a chip skipped and the v5e's peaks
    taken for the CPU's; returns the result object."""
    import jax

    from bench.harness import flops, runner
    cell = cell or tiny_cell(workload)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    v5e = flops.peaks("TPU v5 lite")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runner, "require_chips", lambda n: jax.devices())
        mp.setattr(flops, "peaks", lambda kind: v5e)
        res = runner.execute(cell, args, ROOT, time.monotonic(), **run_kw)
    res.pop("_info")
    return res
