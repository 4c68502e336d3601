"""The program's own spans and scopes, as ``bench/harness/program.py`` reads
them: the engine's phase spans in a profiler trace taken on the CPU, the
host self time and gap names on synthetic planes, and the scope of each
compiled instruction on a recorded HLO snippet."""
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench.harness import program, xplane

DATA = Path(__file__).parent / "data"


# ---------------------------------------------------------------------------
# the engine's spans in a real profiler trace (CPU)


def test_engine_phase_spans_in_a_profiler_trace(smoke_serving_setup,
                                                tmp_path):
    from jax.profiler import ProfileData

    from repro.serving import Engine, synthetic_trace
    cfg, qcfg, mcfg, params = smoke_serving_setup
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32,
                 page_size=4, alloc_policy="ondemand")
    trace = synthetic_trace(cfg, requests=3, prompt_len=6, gen_len=4,
                            lengths="uniform", seed=3)
    with jax.profiler.trace(str(tmp_path)):
        agg = eng.run(trace)
    assert agg["completed"] == 3
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = program.program_spans(ProfileData.from_file(str(path)).planes)
    names = [n for n, _, _, _ in spans]
    phases = eng.phase_stats()
    for name, row in phases.items():
        assert names.count("repro." + name) == row["count"], name
    assert phases["engine.decode.wait"]["count"] == eng.decode_steps
    assert phases["engine.prefill"]["count"] == eng.prefills == 3
    assert eng.decode_rows >= eng.decode_steps

    order = ["repro.engine.decode.stage", "repro.engine.decode.launch",
             "repro.engine.decode.wait", "repro.engine.emit"]
    decode_steps = 0
    for name, s, e, _ in spans:
        if name != "repro.engine.step":
            continue
        kids = [(n, a, b) for n, a, b, _ in spans
                if n in order and s <= a and b <= e]
        if not kids:
            continue
        decode_steps += 1
        assert [n for n, _, _ in kids] == order
        assert all(b1 <= a2 for (_, _, b1), (_, a2, _) in zip(kids,
                                                             kids[1:]))
    assert decode_steps == eng.decode_steps

    prefills = [args for n, _, _, args in spans
                if n == "repro.engine.prefill"]
    assert sorted(a["rid"] for a in prefills) == [0, 1, 2]
    assert all(a["bucket"] >= 6 and a["n_cached"] == 0 for a in prefills)


# ---------------------------------------------------------------------------
# synthetic planes


def _ev(name, start_ms, end_ms, **stats):
    return SimpleNamespace(name=name, start_ns=start_ms * 1e6,
                           duration_ns=(end_ms - start_ms) * 1e6,
                           stats=list(stats.items()))


def _planes(host, modules, ops):
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    return [
        SimpleNamespace(name="/host:CPU", lines=[line("python", host)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line(xplane.MODULES_LINE, modules), line(xplane.OPS_LINE, ops)]),
    ]


# two decode steps, a step that only admits, and one that the window cuts
HOST = [
    _ev("bench.trace_window", 0, 40),
    _ev("bench.engine_step", 1, 14), _ev("repro.engine.step", 1, 14),
    _ev("repro.engine.decode.stage", 2, 4, rows=3),
    _ev("repro.engine.decode.launch", 4, 5),
    _ev("repro.engine.decode.wait", 5, 12), _ev("repro.engine.emit", 12, 13),
    _ev("bench.engine_step", 16, 30), _ev("repro.engine.step", 16, 30),
    _ev("repro.engine.prefill", 16.5, 20, rid=7, bucket=16, n_cached=0),
    _ev("repro.engine.prefill.wait", 18, 20),
    _ev("repro.engine.decode.stage", 20, 21.5, rows=4),
    _ev("repro.engine.decode.launch", 21.5, 22),
    _ev("repro.engine.decode.wait", 22, 28), _ev("repro.engine.emit", 28, 29),
    _ev("repro.engine.step", 31, 33), _ev("repro.engine.admit", 31, 32),
    _ev("repro.engine.step", 38, 45), _ev("repro.engine.decode.wait", 39, 44),
]
DEVICE_OPS = [_ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 5, 12),
              _ev("prefill_op.1", 19, 20), _ev("fusion.1", 22, 28)]
MODULES = [_ev("jit_decode_sample(1)", 5, 12), _ev("jit_prefill(2)", 19, 20),
           _ev("jit_decode_sample(3)", 22, 28)]


def test_engine_host_time_subtracts_the_waits():
    planes = _planes(HOST, MODULES, DEVICE_OPS)
    spans = [(n, s, e) for n, s, e, _ in program.program_spans(planes)]
    ms, steps = program.engine_host(spans, (0.0, 0.040))
    # 13 ms less a 7 ms wait, and 14 ms less 2 + 6 ms of waits; the step
    # without a decode and the one the window cuts are left out
    assert steps == 2
    assert ms == pytest.approx((6.0 + 6.0) / 2)
    assert program.engine_host(spans, (0.0, 0.010)) is None


def test_gaps_are_named_by_the_innermost_program_span():
    planes = _planes(HOST, MODULES, DEVICE_OPS)
    red = xplane.reduce(planes)
    # xplane alone names the gap before the first decode by the
    # benchmark's span around the whole step
    assert ("bench.engine_step", pytest.approx(0.005)) in red.gaps
    spans = xplane.host_spans(planes) + [
        (n, s, e) for n, s, e, _ in program.program_spans(planes)]
    # the same gaps, each named by the innermost span, the program's
    # phase where one covers it
    assert program.named_gaps(red, spans) == [
        ("host", pytest.approx(0.012)), ("host", pytest.approx(0.007)),
        ("repro.engine.decode.stage", pytest.approx(0.005)),
        ("repro.engine.decode.stage", pytest.approx(0.002))]
    assert sorted(g for _, g in red.gaps) == pytest.approx(
        [0.002, 0.005, 0.007, 0.012])
    assert program.idle_by_span(program.named_gaps(red, spans)) == \
        pytest.approx({"host": 0.019, "repro.engine.decode.stage": 0.007})


# ---------------------------------------------------------------------------
# scopes on a recorded HLO snippet (train step, TPU v5e, smoke config)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(attention)/core/dot_general", "attention/core"),
    ("jit(train_step)/transpose(jvp(attention))/core/dot_general",
     "attention/core"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/core/exp", "attention/core"),
    ("jit(decode_sample)/while/body/closed_call/attention/kv_write/scatter",
     "attention/kv_write"),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head"),
    ("jit(train_step)/optimizer/jit(madam_update_packed_pallas)/pallas_call",
     "optimizer"),
    ("jit(train_step)/jvp()/while/body/dynamic_update_slice", None),
    ("jit(f)/attention/broadcast_in_dim;jit(f)/attention/squeeze",
     "attention"),
    ("", None),
])
def test_scope_of_unwraps_transforms(op_name, scope):
    assert program.scope_of(op_name) == scope


def test_instruction_scopes_of_a_recorded_program():
    text = (DATA / "scopes.hlo.txt").read_text()
    scopes = program.instruction_scopes(text)
    assert scopes["convolution.45"] == "head"
    assert scopes["fusion.949"] == "attention/core"
    assert scopes["convolution-base-dilated.18.clone.3"] == "attention/core"
    assert scopes["lns_qmatmul_pallas.148"] == "mlp"
    # a fusion without a scope of its own votes by its fused computation:
    # the scan's residual write carries none, the gradient norm's
    # reduction fused into the gradient quantizer takes its scope
    assert scopes["bitcast_dynamic-update-slice_fusion.12"] is None
    assert scopes["multiply_reduce_fusion.10"] == "grad_quantize"


def test_device_scopes_per_whole_run():
    text = (DATA / "scopes.hlo.txt").read_text()
    dur = {"convolution.45": 1, "fusion.949": 2,
           "convolution-base-dilated.18.clone.3": 3,
           "lns_qmatmul_pallas.148": 4,
           "bitcast_dynamic-update-slice_fusion.12": 5,
           "multiply_reduce_fusion.10": 6}
    ops, modules, t = [], [], 1.0
    for _ in range(2):   # two whole runs of 21 ms, then one the window cuts
        modules.append(_ev("jit_train_step(1)", t, t + 21))
        for name, d in dur.items():
            ops.append(_ev(name, t, t + d))
            t += d
        t += 1
    modules.append(_ev("jit_train_step(1)", t, t + 21))
    ops.append(_ev("fusion.949", t, t + 2))
    host = [_ev("bench.trace_window", 0, t + 10)]
    planes = _planes(host, modules, ops)
    red = xplane.reduce(planes)
    per = program.device_scopes(planes, red, "jit_train_step", text)
    assert per == pytest.approx({"attention/core": 5.0, "head": 1.0,
                                 "mlp": 4.0, "grad_quantize": 6.0,
                                 "unscoped": 5.0})
    assert program.top_level(per)["attention"] == pytest.approx(5.0)
    fams = program.scope_families(planes, red, "jit_train_step", text)
    assert fams["unscoped"] == pytest.approx(
        {"bitcast_dynamic-update-slice_fusion": 5.0})
    assert fams["attention/core"] == pytest.approx(
        {"fusion": 2.0,
         xplane.op_family("convolution-base-dilated.18.clone.3"): 3.0})
    # a program without scopes reads as unscoped, and no run as nothing
    assert program.device_scopes(planes, red, "jit_train_step",
                                 "HloModule m\n") == pytest.approx(
        {"unscoped": 21.0})
    assert program.device_scopes(planes, red, "jit_other", text) is None
