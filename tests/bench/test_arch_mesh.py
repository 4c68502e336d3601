"""A four-chip cell's mesh, on four host devices: the tiny cells run
``correct`` on a (1, 4) mesh with their weights made sharded."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_tiny_cell_on_four_host_devices(kind):
    """The tiny cell at four chips on a (1, 4) mesh of four CPU devices:
    ``correct``, and every parameter leaf made in its rule's sharding."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "mesh4_tiny.py"), kind],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["mesh"] == {"data": 1, "model": 4}
    assert out["correct"] is True, out["checks"]
    assert out["misplaced"] == [] and out["whole_where_split"] == []
    if kind == "serve":
        # d_ff 1536 splits four ways: the MLP's up and gate columns
        assert out["split"] >= 2
    else:
        # smollm's training rules replicate the weights; the batch splits
        assert out["batch_rows_per_device"] == 1
