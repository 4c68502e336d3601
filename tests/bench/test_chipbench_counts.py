"""Operation and byte counts against hand counts at smollm-135m's widths,
the peaks table, the generators' repeatability from the seed, and the
shape of BENCHMARK.json against the files it names."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def smollm():
    from bench.harness import reference
    cfg = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    return reference.Dims.from_model(cfg["model"])


def test_matmul_params_at_smollm_widths(smollm):
    from bench.harness import flops
    # per layer: q 576x576, k and v 576x192, o 576x576, up/gate/down
    # 576x1536 each; the tied head 576x49152
    layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert layer == 3_538_944
    assert flops.matmul_params(smollm) == 30 * layer + 49152 * 576
    assert flops.matmul_params(smollm) == 134_479_872


def test_train_and_decode_flops(smollm):
    from bench.harness import flops
    n = 134_479_872
    assert flops.train_flops_per_token(smollm, 2048) == \
        6 * n + 12 * 30 * 576 * 2048
    assert flops.decode_flops(smollm, [100, 300]) == \
        2 * (2 * n) + 4 * 30 * 576 * 400
    assert flops.decode_flops(smollm, []) == 0


def test_kernel_work():
    from bench.harness import flops
    f, b = flops.qmatmul_work(16384, 576, 192)
    assert f == 2 * 16384 * 576 * 192
    assert b == 16384 * 576 + 576 * 192 + 16384 * 192 * 4
    f, b = flops.madam_work(576 * 576, 2)
    assert f == 0 and b == 576 * 576 * (2 + 4 + 4 + 2 + 4)


def test_peaks_are_keyed_by_device_kind():
    from bench.harness import flops
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert flops.least_time(197e12, 0, pk) == pytest.approx(1.0)
    assert flops.least_time(0, 819e9, pk) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_traffic_repeats_from_the_seed():
    from bench.harness import traffic
    mix = json.loads((ROOT / "bench/traffic/short.json").read_text())
    seed = 2 ** 40 + 3
    a = traffic.plan(mix, seed, 10.0, 49152)
    b = traffic.plan(mix, seed, 10.0, 49152)
    assert [(p.due, p.prompt, p.max_new_tokens, p.greedy) for p in a] == \
        [(p.due, p.prompt, p.max_new_tokens, p.greedy) for p in b]
    c = traffic.plan(mix, seed + 1, 10.0, 49152)
    # another seed: the same amount of work in another order
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt)
                                                      for p in c)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in c)
    assert [p.prompt for p in a] != [p.prompt for p in c]
    n_lead = round(mix["rate"] * mix["lead_in_s"])
    assert len(a) == n_lead + round(mix["rate"] * 10.0)
    window = [p for p in a if p.due >= mix["lead_in_s"]]
    assert len(window) == len(a) - n_lead
    # the window's own work is the same for every seed
    assert sum(p.max_new_tokens for p in window) == sum(
        p.max_new_tokens for p in c if p.due >= mix["lead_in_s"])
    assert all(0 <= p.due < mix["lead_in_s"] + 10.0 for p in a)
    lens = [len(p.prompt) for p in a]
    assert min(lens) >= mix["prompt"]["min"]
    assert max(lens) <= mix["prompt"]["max"]
    assert abs(np.median(lens) - mix["prompt"]["median"]) <= 2


def test_balanced_order_keeps_every_stretch_representative():
    from bench.harness import traffic
    rng = np.random.default_rng(5)
    vals = np.arange(64)
    out = traffic.balanced_order(vals, rng)
    assert sorted(out.tolist()) == vals.tolist()
    for j in range(0, 64, traffic.STRATA):
        block = out[j:j + traffic.STRATA]
        # one value from each quantile band of four
        assert sorted(v // 4 for v in block) == list(range(16))


def test_tokens_and_weights_repeat_from_the_seed():
    import jax.numpy as jnp

    from bench.harness import data, lnsgen
    w = lnsgen.seed_words(2 ** 33 + 1)
    a = data.make_batch(w, jnp.int32(3), batch=4, seq=16, vocab=512)
    b = data.make_batch(w, jnp.int32(3), batch=4, seq=16, vocab=512)
    c = data.make_batch(w, jnp.int32(4), batch=4, seq=16, vocab=512)
    assert (np.asarray(a["tokens"]) == np.asarray(b["tokens"])).all()
    assert (np.asarray(a["labels"][:, :-1])
            == np.asarray(a["tokens"][:, 1:])).all()
    assert not (np.asarray(a["tokens"]) == np.asarray(c["tokens"])).all()
    rows = {tuple(r) for r in np.asarray(a["tokens"]).tolist()}
    assert len(rows) == 4
    k = lnsgen.root_key(jnp.asarray(w), 1)
    w1, s1 = lnsgen.packed_stack(k, 3, (32, 16), 0.1, 8, 8)
    w2, s2 = lnsgen.packed_slice(__import__("jax").random.fold_in(k, 2),
                                 (32, 16), 0.1, 8, 8)
    assert (np.asarray(w1[2]) == np.asarray(w2)).all()
    assert (np.asarray(s1[2]) == np.asarray(s2)).all()
    with pytest.raises(ValueError):
        lnsgen.seed_words(-1)


def test_words_decode_and_regrid():
    import jax.numpy as jnp

    from bench.harness import lnsgen
    z = jnp.asarray([[0.5, -0.25], [1.0, 0.125]], jnp.float32)
    w, s = lnsgen.lns_words(z, 8, 8)
    assert np.asarray(s).tolist() == [[1.0, 0.25]]
    assert np.allclose(np.asarray(lnsgen.decode_words(w, s, 8, 8)),
                       np.asarray(z))
    w16, s16 = lnsgen.lns_words(z, 16, 2048)
    w8 = lnsgen.regrid_words(w16, (16, 2048), (8, 8))
    assert (np.asarray(w8) == np.asarray(w)).all()


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_every_file_it_needs():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        lim = json.loads((ROOT / "bench/limits" /
                          f"{w['name']}.json").read_text())["limits"]
        assert lim and all(v > 0 for v in lim.values())
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert NAME.match(m["name"])
        assert m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
