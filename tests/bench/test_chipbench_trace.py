"""The trace reduction and the kernel-call reader on a trace recorded on a
TPU v5e (``bench/record_trace_fixture.py``): three runs of one program
holding the packed-LNS matmul and the packed Madam update, each inside a
``bench.train_step`` span and followed by a 2 ms ``bench.idle_wait``."""
from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def red():
    from bench.harness import xplane
    return xplane.load(str(DATA / "fixture.xplane.pb"))


def test_busy_is_the_union_of_device_ops(red):
    from bench.harness import xplane
    ops = [(o.start, o.start + o.dur) for o in red.ops]
    merged = xplane.union(ops)
    assert red.devices == ["/device:TPU:0"]
    assert len(red.ops) == 12          # 4 ops in each of 3 runs
    assert red.busy_s == pytest.approx(sum(e - s for s, e in merged))
    # the ops of one run follow each other without overlapping
    assert red.busy_s == pytest.approx(sum(o.dur for o in red.ops))
    assert 0 < red.busy_s < red.window_s


def test_union_merges_overlaps():
    from bench.harness import xplane
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.clip((0, 5), (1, 2)) == (1, 2)
    assert xplane.clip((0, 1), (2, 3)) is None


def test_per_op_time_and_modules(red):
    top = dict(red_top(red))
    assert set(top) == {"madam_update_packed_pallas", "lns_qmatmul_pallas",
                        "slice", "pad"}
    per_kernel = sum(o.dur for o in red.ops
                     if o.name == "lns_qmatmul_pallas.1")
    assert top["lns_qmatmul_pallas"] == pytest.approx(per_kernel)
    assert red.module_runs == {"jit_prog": 3}
    assert all(o.module == "jit_prog" for o in red.ops)


def red_top(red):
    from bench.harness import xplane
    return xplane.top_ops(red)


def test_gaps_are_named_by_the_host_span_around_them(red):
    # the two long gaps between runs fall in the host's 2 ms sleep
    long = [g for g in red.gaps if g[1] > 1e-3]
    assert len(long) == 2
    assert {name for name, _ in long} == {"bench.idle_wait"}
    assert all(2e-3 < s < 5e-3 for _, s in long)


def test_instruction_names_from_hlo_text():
    from bench.harness import xplane
    assert xplane.instruction_name(
        "%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop") == "fusion.3"
    assert xplane.instruction_name("copy.1") == "copy.1"


def test_kernel_calls_read_real_shapes_from_pads():
    from bench.harness import hlo
    text = (DATA / "fixture.hlo.txt").read_text()
    calls = hlo.kernel_calls(text)
    assert hlo.module_name(text) == "jit_prog"
    q = calls["lns_qmatmul_pallas.1"]
    assert q["kernel"] == "lns_qmatmul_pallas"
    # 200 rows padded to 256 for the kernel
    assert q["operands"][0] == ("u8", (200, 512), (256, 512))
    m = calls["madam_update_packed_pallas.1"]
    assert m["kernel"] == "madam_update_packed_pallas"
    assert m["operands"][1][0] == "u16"


def test_roofline_share_of_the_fixture(red):
    from bench.harness import flops, hlo, readers
    text = (DATA / "fixture.hlo.txt").read_text()
    calls = hlo.apply_real_shapes(hlo.kernel_calls(text), {})
    rec = {"trace": red, "programs": {"jit_prog": calls},
           "peaks": flops.peaks("TPU v5 lite")}
    share = readers.kernel_roofline(rec, "lns_qmatmul_pallas", "jit_prog",
                                    readers.qmatmul_call_work)
    f, b = flops.qmatmul_work(200, 512, 384)
    t = sum(o.dur for o in red.ops if o.name == "lns_qmatmul_pallas.1")
    expect = 100 * 3 * flops.least_time(f, b, rec["peaks"]) / t
    assert share == pytest.approx(expect)
    assert 0 < share < 100
    assert readers.kernel_roofline(rec, "lns_qmatmul_pallas", "decode",
                                   readers.qmatmul_call_work) is None
