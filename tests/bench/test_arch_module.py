"""A configuration's architecture module: the model keys it applies or
refuses at load, the module the runs call, and the cell's mesh."""
import json
import sys
from pathlib import Path

import pytest

import counting_arch
from chipbench_tiny import SERVE_CELL, TRAIN_CELL, run_tiny, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
SMOLLM = ROOT / "bench/configs/smollm-135m.json"


def load(tmp_path, model=None, drop=(), chips=1, **config):
    """The train cell loaded with its configuration changed: ``model``
    keys set, ``drop`` keys removed, top-level ``config`` keys set."""
    from bench.harness import spec
    cfg = json.loads(SMOLLM.read_text())
    cfg["model"].update(model or {})
    for k in drop:
        del cfg["model"][k]
    cfg.update(config)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = str(path)
    for w in bench["workloads"]:
        w["chips"] = chips
    return spec.load_cell(ROOT, TRAIN_CELL, bench)


@pytest.fixture
def counting(monkeypatch):
    """``counting_arch`` as the module ``counting_arch.py`` names, its
    counters cleared."""
    monkeypatch.setitem(sys.modules, "bench.harness.counting_arch",
                        counting_arch)
    counting_arch.CALLS.clear()
    return counting_arch


@pytest.mark.parametrize("workload", [TRAIN_CELL, SERVE_CELL])
def test_smollm_loads_through_the_dense_module(workload):
    from bench.harness import reference, spec
    from repro.configs import get_config
    cell = spec.load_cell(ROOT, workload)
    assert cell.arch is reference
    assert spec.module_of(cell.dims) is reference
    assert cell.make_mesh([object()]) is None
    cfg = spec.arch_config(cell.config)
    for key, value in cell.model.items():
        if key in reference.MODEL_KEYS:
            assert getattr(cfg, reference.MODEL_KEYS[key]) == value, key
        else:
            assert key in reference.NOT_APPLIED, key
    base = get_config("smollm-135m")
    # the file changes the registry entry in the norm's epsilon alone
    changed = {f for f in base.__dataclass_fields__
               if getattr(base, f) != getattr(cfg, f)}
    assert changed == {"norm_eps"}
    assert cfg.act_fn == "silu" and cfg.norm_eps == 1e-5


@pytest.mark.parametrize("key, value", [
    ("n_routed_experts", 64), ("num_experts_per_tok", 6),
    ("kv_lora_rank", 512), ("sliding_window", 4096),
    ("partial_rotary_factor", 0.5)])
def test_unmapped_key_is_refused_by_name(tmp_path, key, value):
    with pytest.raises(ValueError, match=key):
        load(tmp_path, model={key: value})


@pytest.mark.parametrize("key, value", [
    ("tie_word_embeddings", False), ("tie_word_embeddings", None),
    ("attention_bias", True), ("hidden_act", "gelu")])
def test_dense_module_refuses_what_it_does_not_compute(tmp_path, key,
                                                       value):
    if value is None:
        kw = {"drop": (key,)}
    else:
        kw = {"model": {key: value}}
    with pytest.raises(ValueError, match=key):
        load(tmp_path, **kw)


def test_dense_module_applies_what_it_computes(tmp_path):
    from bench.harness import spec
    cell = load(tmp_path, model={"attention_bias": False})
    assert spec.arch_config(cell.config).qkv_bias is False


def test_expert_and_latent_keys_reach_the_arch_config(tmp_path, counting):
    from bench.harness import spec
    keys = {"n_routed_experts": 64, "num_experts_per_tok": 6,
            "moe_intermediate_size": 1408, "first_k_dense_replace": 1,
            "kv_lora_rank": 512, "qk_rope_head_dim": 64}
    cell = load(tmp_path, model=keys, reference="counting_arch.py")
    assert cell.arch is counting
    cfg = spec.arch_config(cell.config)
    for key, value in keys.items():
        assert getattr(cfg, counting.MODEL_KEYS[key]) == value, key
    assert isinstance(cell.dims, counting.Dims)


def test_mapped_field_unequal_to_the_file_is_refused(tmp_path, counting,
                                                     monkeypatch):
    """Two keys onto one field: whichever lands, the other's value is not
    what the program runs, and the check after applying names it."""
    from bench.harness import spec
    monkeypatch.setitem(counting.MODEL_KEYS, "num_local_experts",
                        "num_experts")
    cell = load(tmp_path, model={"n_routed_experts": 64,
                                 "num_local_experts": 8},
                reference="counting_arch.py")
    with pytest.raises(ValueError, match="num_experts"):
        spec.arch_config(cell.config)


@pytest.mark.parametrize("name", ["../configs/x.py", "reference",
                                  "no_such_module.py"])
def test_reference_names_a_module_under_the_harness(tmp_path, name):
    with pytest.raises(ValueError, match="reference"):
        load(tmp_path, reference=name)


@pytest.mark.parametrize("chips, mesh", [
    (4, None), (4, {"data": 1, "model": 2}), (1, {"data": 1, "model": 4}),
    (4, {"data": 1, "model": 4, "pod": 1}), (4, {"model": 4})])
def test_mesh_must_span_the_cells_chips(tmp_path, chips, mesh):
    kw = {} if mesh is None else {"mesh": mesh}
    with pytest.raises(ValueError, match="mesh"):
        load(tmp_path, chips=chips, **kw)


def test_mesh_is_taken_from_the_configuration(tmp_path):
    from bench.harness import spec
    cell = load(tmp_path, chips=4, mesh={"data": 2, "model": 2})
    assert spec.mesh_shape(cell.config, cell.chips) == (2, 2)


def dense_unreachable(monkeypatch):
    """Make every architecture function of the dense module raise: only a
    call through the configured module then reaches its arithmetic."""
    from bench.harness import reference as dense

    def unreachable(*a, **kw):
        raise AssertionError("the dense module was called directly")
    for name in ("train_readings", "serve_logits", "train_flops_per_token",
                 "decode_row_flops"):
        monkeypatch.setattr(dense, name, unreachable)
    monkeypatch.setattr(dense.Dims, "from_model", classmethod(unreachable))


def test_train_run_calls_only_the_configured_module(counting, monkeypatch):
    cell = tiny_cell(TRAIN_CELL, reference="counting_arch.py")
    dense_unreachable(monkeypatch)
    res = run_tiny(TRAIN_CELL, seconds=0.3, cell=cell)
    assert res["correct"] is True, res["checks"]
    calls = counting.CALLS
    assert calls["train_readings"] == 1 and calls["from_model"] >= 1
    from bench.harness import flops
    assert flops.train_flops_per_token(cell.dims, 32) > 0
    assert calls["train_flops_per_token"] == 1


def test_serve_run_calls_only_the_configured_module(counting, monkeypatch):
    cell = tiny_cell(SERVE_CELL, reference="counting_arch.py")
    dense_unreachable(monkeypatch)
    res = run_tiny(SERVE_CELL, trace=1, cell=cell)
    assert res["correct"] is True, res["checks"]
    calls = counting.CALLS
    assert calls["serve_logits"] >= 1 and calls["from_model"] >= 1
    # the traced decode steps' model FLOPs, row by row
    assert calls["decode_row_flops"] >= 1
