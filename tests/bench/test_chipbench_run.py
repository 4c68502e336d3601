"""The result line a run prints, and the refusals: no TPU, or no program
beside the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench_tiny import SERVE_CELL, TRAIN_CELL, run_tiny

ROOT = Path(__file__).resolve().parents[2]


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _check_line(res, workload, trace):
    bench = _bench()
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert ("breakdown" in keys) == bool(trace)
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] == 0
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench[group]
               if workload in m.get("workloads", [workload])}
    for name, m in res["metrics"].items():
        assert allowed[name] == m["unit"]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(res["metrics"]) == set(allowed)
        assert res["metrics"]["setup_s"]["value"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        bd = res["breakdown"]
        assert set(bd) == {"device_ops", "idle_gaps"}
        assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def test_train_result_line():
    res = run_tiny(TRAIN_CELL)
    _check_line(res, TRAIN_CELL, 0)
    assert res["correct"] is True
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(res["checks"]) == {"grad_norm_gap", "change_norm_gap"}


def test_serve_result_line_traced():
    res = run_tiny(SERVE_CELL, trace=1)
    _check_line(res, SERVE_CELL, 1)
    assert res["correct"] is True
    assert set(res["checks"]) == {"served_logit_gap"}
    # counters and host timestamps are read on any platform
    assert res["metrics"]["decode_batch_mean.serve"]["value"] >= 1
    # no chip, no device numbers: the trace readers return nothing
    assert "idle_share.serve" not in res["metrics"]


def _run(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN_CELL,
         "--seed", str(2 ** 35 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    bench = _bench()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.mark.parametrize("seconds", [0.5])
def test_train_window_counts_whole_steps(seconds):
    res = run_tiny(TRAIN_CELL, seconds=seconds)
    assert res["attempted"] >= 4      # three checked steps, then the window
