"""The tiny train or serve cell at four chips, on a (1, 4) mesh of four CPU
devices, and where its weights land.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/bench/mesh4_tiny.py train|serve

Prints one JSON line: the run's ``correct`` and checks, and, for the
weights the cell's set-up makes, the leaves whose sharding is not their
rule's (``misplaced``), those a device holds whole although their rule
splits them (``whole_where_split``) and how many are split (``split``).
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

MESH = {"data": 1, "model": 4}


def placement(tree, expected):
    """(misplaced, whole where split, split) leaf names of ``tree``
    against the shardings ``expected``."""
    import jax

    from bench.harness import weights
    misplaced, whole, split = [], [], 0
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    want = jax.tree_util.tree_leaves(expected)
    for (path, leaf), sh in zip(flat, want):
        name = weights.path_str(path)
        if not leaf.sharding.is_equivalent_to(sh, leaf.ndim):
            misplaced.append(name)
        shards = {s.data.shape for s in leaf.addressable_shards}
        if sh.num_devices > 1 and not sh.is_fully_replicated:
            split += 1
            if tuple(leaf.shape) in shards:
                whole.append(name)
    return misplaced, whole, split


def main(kind: str) -> int:
    import jax

    from bench.harness import lnsgen, serve, train, weights
    from chipbench_tiny import SERVE_CELL, TRAIN_CELL, run_tiny, tiny_cell
    workload = TRAIN_CELL if kind == "train" else SERVE_CELL
    cell = tiny_cell(workload, chips=4, mesh=dict(MESH))
    res = run_tiny(workload, cell=cell, seconds=1.0)
    mesh = cell.make_mesh(jax.devices())
    seed = 2 ** 35 + 3
    if kind == "train":
        job = train.TrainJob(cell, mesh=mesh)
        words = lnsgen.seed_words(seed)
        state = job.state_fn(words)
        expected = weights.train_state_shardings(state, mesh, job.rules)
        got = placement(state, expected)
        batch = job.batch_fn(words, jax.numpy.int32(0))
        rows = {s.data.shape[0] for s in batch["tokens"].addressable_shards}
        extra = {"batch_rows_per_device": max(rows)}
    else:
        from repro.distributed.params_sharding import params_shardings
        from repro.distributed.sharding import serving_rules
        run = serve.ServeRun(cell, seed, mesh=mesh)
        params = run.make_params()
        expected = params_shardings(params, mesh,
                                    serving_rules(run.cfg, mesh),
                                    serving=True)
        got = placement(params, expected)
        extra = {}
    misplaced, whole, split = got
    print(json.dumps({"devices": len(jax.devices()),
                      "mesh": dict(mesh.shape), "correct": res["correct"],
                      "checks": res["checks"], "misplaced": misplaced,
                      "whole_where_split": whole, "split": split, **extra}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
