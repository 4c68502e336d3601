"""Logical sharding rules, param-tree axis assignment, mesh resolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.params_sharding import (batch_shardings,
                                               cache_logical_axes,
                                               opt_logical_axes,
                                               params_logical_axes,
                                               tree_shardings)
from repro.distributed.sharding import (LOGICAL_RULES, shard, shard_ctx,
                                        spec_for)
from repro.launch.mesh import make_mesh
from repro.models import ArchConfig, init_params
from repro.optim.madam import LNSWeight, MadamConfig, init_lns_params, madam_lns


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_spec_resolution_drops_missing_axes():
    mesh = _mesh()  # no "pod" axis
    with shard_ctx(mesh):
        spec = spec_for(("batch", "embed"))
        assert spec == P("data", None)  # ("pod","data") -> "data"


def test_shard_noop_without_mesh(key):
    x = jax.random.normal(key, (4, 4))
    y = shard(x, "batch", "embed")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shard_applies_constraint_under_mesh(key):
    x = jax.random.normal(key, (4, 4))
    with shard_ctx(_mesh()):
        y = jax.jit(lambda x: shard(x, "batch", "mlp"))(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_rule_overrides(key):
    with shard_ctx(_mesh(), {"mlp": None}):
        assert spec_for((None, "mlp")) == P(None, None)
    with shard_ctx(_mesh()):
        assert spec_for((None, "mlp")) == P(None, "model")


def test_params_logical_axes_known_paths(key):
    cfg = ArchConfig(name="t", family="dense", num_layers=4, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    params = init_params(key, cfg)
    axes = params_logical_axes(params)
    assert axes["embed"]["tok"] == ("vocab", "embed")
    assert axes["embed"]["head"] == ("embed", "vocab")
    # stacked period weights get the leading "stack" axis
    assert axes["period"]["pos0"]["mlp"]["up"] == ("stack", "embed", "mlp")
    assert axes["period"]["pos0"]["attn"]["wq"] == ("stack", "embed", "qkv_out")
    # norms are replicated (the "stack" prefix resolves to None anyway)
    assert axes["period"]["pos0"]["ln1"] == (None, None)


def test_lns_weight_axes_and_shardings(key):
    cfg = ArchConfig(name="t", family="dense", num_layers=2, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    mcfg = MadamConfig()
    params = init_lns_params(init_params(key, cfg), mcfg)
    axes = params_logical_axes(params)
    lw = axes["period"]["pos0"]["mlp"]["up"]
    assert isinstance(lw, LNSWeight)
    assert lw.packed == ("stack", "embed", "mlp")
    # scale has a size-1 axis -> unsharded there
    assert lw.scale == ("stack", None, "mlp")
    sh = tree_shardings(axes, _mesh())
    leaf = sh["period"]["pos0"]["mlp"]["up"]
    assert leaf.packed.spec == P(None, None, "model")


def test_opt_axes_factored(key):
    cfg = ArchConfig(name="t", family="dense", num_layers=2, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    mcfg = MadamConfig(factored=True)
    params = init_lns_params(init_params(key, cfg), mcfg)
    init, _ = madam_lns(mcfg)
    opt = init(params)
    oax = opt_logical_axes(params, opt)
    g2 = oax.g2["period"]["pos0"]["mlp"]["up"]
    assert g2 == {"r": ("stack", "embed"), "c": ("stack", "mlp")}


def test_cache_axes(key):
    from repro.models import init_caches
    cfg = ArchConfig(name="t", family="dense", num_layers=2, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    caches = init_caches(2, 16, cfg)
    axes = cache_logical_axes(caches)
    # k carries BOTH kv_seq and kv_heads: training rules give the model
    # axis to kv_seq (first wins), serving rules flip it to the head dim
    assert axes["period"]["pos0"]["k"] == ("stack", "batch", "kv_seq",
                                           "kv_heads", None)
    with shard_ctx(_mesh()):
        assert spec_for(axes["period"]["pos0"]["k"]) == \
            P(None, "data", "model", None, None)


def test_spec_for_dedups_mesh_axes_first_wins():
    with shard_ctx(_mesh()):
        # kv_seq and kv_heads both map to "model": the earlier dim keeps it
        assert spec_for(("batch", "kv_seq", "kv_heads", None)) == \
            P("data", "model", None, None)
    # serving-style override frees the axis for the later dim
    with shard_ctx(_mesh(), {"kv_seq": None}):
        assert spec_for(("batch", "kv_seq", "kv_heads", None)) == \
            P("data", None, "model", None)


def test_spec_for_tuple_axes_partially_present():
    # ("pod","data","model") with no "pod" in the mesh -> remaining axes
    mesh = _mesh()
    with shard_ctx(mesh):
        assert spec_for(("batch_full",)) == P(("data", "model"))
        # a tuple whose members were all consumed upstream collapses to None
        assert spec_for(("batch", "batch_full")) == P("data", "model")


def test_shard_ctx_nesting_and_restore_on_exception():
    mesh = _mesh()
    with shard_ctx(mesh, {"mlp": None}):
        assert spec_for((None, "mlp")) == P(None, None)
        with shard_ctx(mesh, {"mlp": "model", "embed": "data"}):
            assert spec_for(("embed", "mlp")) == P("data", "model")
        # inner overrides rolled back, outer still active
        assert spec_for((None, "mlp")) == P(None, None)
        with pytest.raises(RuntimeError):
            with shard_ctx(mesh, {"mlp": "model"}):
                assert spec_for((None, "mlp")) == P(None, "model")
                raise RuntimeError("boom")
        # exception unwound the inner context, not the outer one
        assert spec_for((None, "mlp")) == P(None, None)
    from repro.distributed.sharding import current_mesh
    assert current_mesh() is None


def test_lns_weight_packed_and_scale_specs_consistent(key):
    """spec_for over a packed LNSWeight pytree: the scale's non-unit dims
    resolve exactly like the packed words' (a shard never pairs its local
    codes with another shard's scale column)."""
    cfg = ArchConfig(name="t", family="dense", num_layers=2, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    params = init_lns_params(init_params(key, cfg), MadamConfig())
    axes = params_logical_axes(params)
    mesh = _mesh()
    sh = tree_shardings(axes, mesh)

    def check(ax, lf):
        if not isinstance(ax, LNSWeight):
            return
        packed_spec = spec_for(ax.packed, mesh)
        scale_spec = spec_for(ax.scale, mesh)
        assert lf.packed.spec == packed_spec
        assert lf.scale.spec == scale_spec
        # wherever the scale is non-unit it must match the packed spec
        for i, (pa, sa) in enumerate(zip(ax.packed, ax.scale)):
            if sa is not None:
                assert sa == pa

    is_axes_leaf = lambda x: isinstance(x, LNSWeight) or (
        isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                     for a in x))
    jax.tree.map(check, axes, sh, is_leaf=is_axes_leaf)


def test_batch_shardings(key):
    mesh = _mesh()
    b = {"tokens": jnp.zeros((4, 8), jnp.int32),
         "patches": jnp.zeros((4, 2, 16))}
    sh = batch_shardings(b, mesh)
    assert sh["tokens"].spec == P("data", None)
    assert sh["patches"].spec == P("data", None, None)


def test_unknown_logical_axis_raises():
    with shard_ctx(_mesh()):
        with pytest.raises(KeyError):
            spec_for(("no_such_axis",))


def test_make_host_mesh_raises_on_oversubscription():
    """A mesh request larger than the platform must raise (not silently
    collapse to (n, 1) — that let CI mesh legs pass vacuously)."""
    from repro.launch.mesh import make_host_mesh
    n = jax.device_count()
    with pytest.raises(ValueError) as ei:
        make_host_mesh(data=n, model=2)
    msg = str(ei.value)
    assert f"data={n}, model=2" in msg          # requested shape
    assert f"only {n} are available" in msg     # available count
    # the largest satisfiable shape still works
    assert make_host_mesh(data=n, model=1).devices.size == n


def test_serving_rules_divisibility_gates():
    from repro.distributed.sharding import serving_rules
    from repro.models import ArchConfig
    mesh2 = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    div = ArchConfig(name="t", family="dense", num_layers=2, d_model=32,
                     num_heads=4, num_kv_heads=4, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    rules = serving_rules(div, FakeMesh())
    assert rules["kv_heads"] == "model" and rules["qkv_out"] == "model"
    assert rules["mlp"] == "model"
    # equality-critical axes always replicate in serving
    assert rules["batch"] is None and rules["kv_seq"] is None
    assert rules["attn_out"] is None and rules["vocab"] is None

    # smollm-smoke shape: 3 heads / 1 kv head don't divide model=2
    odd = ArchConfig(name="t", family="dense", num_layers=2, d_model=48,
                     num_heads=3, num_kv_heads=1, head_dim=16, d_ff=64,
                     vocab_size=128, dtype="float32")
    rules = serving_rules(odd, FakeMesh())
    assert rules["kv_heads"] is None and rules["qkv_out"] is None
    assert rules["mlp"] == "model"  # d_ff still divides

    # trivial model axis -> nothing sharded at all
    rules = serving_rules(div, mesh2)
    assert all(v is None for v in rules.values())
