"""Observability layer: span ring + Chrome trace contract, phase spans,
step timeline, Prometheus exposition round trip, and the engine/driver
integration (spans for every completed request, torn-read-free /metrics
under scrape concurrency)."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (EngineObserver, Histogram, Phases, SpanRing,
                       StepTimeline, parse_prometheus_text,
                       render_prometheus, validate_chrome_trace)
from repro.obs.spans import CAT_ENGINE, CAT_REQUEST, ENGINE_TID, request_tid
from repro.serving import Engine, synthetic_trace


# ---------------------------------------------------------------------------
# span ring + Chrome trace schema


def _finished_request_ring(rid=0):
    ring = SpanRing(64)
    tid = request_tid(rid)
    ring.name_tid(tid, f"req {rid}")
    ring.complete("queue", CAT_REQUEST, tid, 0.0, 0.1)
    ring.complete("prefill", CAT_REQUEST, tid, 0.1, 0.2)
    ring.complete("decode", CAT_REQUEST, tid, 0.2, 0.9)
    ring.instant("finish", CAT_REQUEST, tid, 0.9,
                 {"reason": "length", "tokens": 8})
    return ring


def test_chrome_trace_round_trip(tmp_path):
    ring = _finished_request_ring()
    path = tmp_path / "t.trace.json"
    ring.export(str(path))
    doc = json.loads(path.read_text())
    per_rid = validate_chrome_trace(doc)
    assert per_rid == {0: {"queue": 1, "prefill": 1, "decode": 1}}
    # timestamps are microseconds, sorted, with thread-name metadata
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    decode = next(e for e in evs if e["name"] == "decode")
    assert decode["ts"] == pytest.approx(0.2e6)
    assert decode["dur"] == pytest.approx(0.7e6)
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine", "req 0"} <= names


def test_validate_rejects_incomplete_traces():
    # no finish marker at all
    ring = SpanRing(16)
    ring.complete("queue", CAT_REQUEST, request_tid(0), 0.0, 0.1)
    with pytest.raises(ValueError, match="no completed request"):
        validate_chrome_trace(ring.to_chrome())
    # finished but missing its decode span
    ring2 = SpanRing(16)
    tid = request_tid(1)
    ring2.complete("queue", CAT_REQUEST, tid, 0.0, 0.1)
    ring2.complete("prefill", CAT_REQUEST, tid, 0.1, 0.2)
    ring2.instant("finish", CAT_REQUEST, tid, 0.3, {"reason": "stop"})
    with pytest.raises(ValueError, match="decode"):
        validate_chrome_trace(ring2.to_chrome())
    # spec required but absent
    with pytest.raises(ValueError, match="spec"):
        validate_chrome_trace(_finished_request_ring().to_chrome(),
                              require_spec=True)
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"not": "a trace"})


def test_span_ring_bounded():
    ring = SpanRing(4)
    for i in range(10):
        ring.complete("s", CAT_ENGINE, 0, float(i), float(i) + 0.5)
    assert len(ring) == 4
    assert ring.dropped == 6
    doc = ring.to_chrome()
    assert doc["otherData"]["dropped_events"] == 6
    ring.clear()
    assert len(ring) == 0 and ring.dropped == 0


# ---------------------------------------------------------------------------
# step timeline


def test_timeline_summary_and_counters():
    tl = StepTimeline(64)
    tl.record("prefill", 0.0, 0.2, running=1, queued=3, emitted=1)
    tl.record("decode", 0.2, 0.3, running=2, emitted=2,
              pages_free=10, pages_cached=1)
    tl.record("spec", 0.3, 0.5, running=2, emitted=3, drafted=8,
              accepted=5)
    s = tl.summary()
    assert s["prefill_steps"] == 1 and s["decode_steps"] == 1
    assert s["spec_steps"] == 1
    assert s["prefill_time_s"] == pytest.approx(0.2)
    assert s["emitted_tokens"] == 6
    assert s["drafted_tokens"] == 8 and s["accepted_tokens"] == 5
    counters = tl.to_chrome_counters()
    assert all(e["ph"] == "C" for e in counters)
    assert any(e["name"] == "slots" for e in counters)
    assert any(e["name"] == "pages" for e in counters)


def test_timeline_wraps_without_allocation():
    tl = StepTimeline(8)
    for i in range(20):
        tl.record("decode", float(i), float(i) + 0.5, emitted=1)
    assert len(tl) == 8
    assert tl.total == 20
    t0s = tl.samples()["t0"]
    # chronological after wrap: the newest 8 rows in order
    assert list(t0s) == [float(i) for i in range(12, 20)]


# ---------------------------------------------------------------------------
# Prometheus exposition


def test_prometheus_round_trip():
    h = Histogram("ttft_seconds", "ttft", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    h.observe(None)
    h.observe(float("nan"))
    assert h.count == 3
    stats = {"completed_total": 3, "slots_busy": 2,
             "tokens_per_s": float("nan"), "flag": True,
             "name": "not-numeric"}
    text = render_prometheus(stats, [h], info={"arch": "smoke",
                                               "backend": "reference",
                                               "skipme": None})
    parsed = parse_prometheus_text(text)
    assert parsed["repro_completed_total"]["type"] == "counter"
    assert parsed["repro_slots_busy"]["type"] == "gauge"
    # NaN rates, bools, and strings never become series
    assert "repro_tokens_per_s" not in parsed
    assert "repro_flag" not in parsed
    info_labels = parsed["repro_build_info"]["samples"][0][0]
    assert info_labels["arch"] == "smoke" and "skipme" not in info_labels
    hist = parsed["repro_ttft_seconds"]
    assert hist["type"] == "histogram"
    by_le = {s.get("le"): v for s, v in hist["samples"]
             if s["__name__"] == "repro_ttft_seconds_bucket"}
    assert by_le == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}


def test_prometheus_parser_rejects_bad_text():
    with pytest.raises(ValueError, match="precedes its TYPE"):
        parse_prometheus_text("repro_x 1\n# TYPE repro_x counter\n")
    with pytest.raises(ValueError, match="non-numeric"):
        parse_prometheus_text("# TYPE repro_x gauge\nrepro_x potato\n")
    with pytest.raises(ValueError, match=r"\+Inf"):
        parse_prometheus_text(
            "# TYPE repro_h histogram\n"
            'repro_h_bucket{le="1"} 1\nrepro_h_sum 0.5\nrepro_h_count 1\n')
    with pytest.raises(ValueError, match="not cumulative"):
        parse_prometheus_text(
            "# TYPE repro_h histogram\n"
            'repro_h_bucket{le="1"} 5\nrepro_h_bucket{le="+Inf"} 3\n'
            "repro_h_sum 0.5\nrepro_h_count 3\n")


# ---------------------------------------------------------------------------
# phase spans


def test_phases_count_and_record_on_the_engine_lane():
    now = [0.0]
    phases = Phases(lambda: now[0])
    for dur in (0.25, 0.5):
        with phases.span("engine.step"):
            with phases.span("engine.decode.wait", rows=3) as sp:
                now[0] += dur
            now[0] += 0.125
    assert (sp.t0, sp.t1) == (0.375, 0.875)
    snap = phases.snapshot()
    assert snap["engine.decode.wait"] == {"count": 2, "total_s": 0.75,
                                          "max_s": 0.5}
    assert snap["engine.step"]["total_s"] == pytest.approx(1.0)
    assert snap["engine.step"]["max_s"] == pytest.approx(0.625)
    # with a ring attached, the same intervals land on the engine lane
    ring = SpanRing(8)
    phases.ring = ring
    with phases.span("engine.emit", rows=2):
        now[0] += 1.0
    assert ring.snapshot() == [("repro.engine.emit", CAT_ENGINE, ENGINE_TID,
                                1.0, 1.0, {"rows": 2})]
    phases.clear()
    assert phases.snapshot() == {}


# ---------------------------------------------------------------------------
# engine integration (real runs on the smoke config)


@pytest.fixture(scope="module")
def obs_run(smoke_serving_setup):
    """One speculative paged run with an observer attached."""
    cfg, qcfg, mcfg, params = smoke_serving_setup
    obs = EngineObserver()
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32,
                 page_size=4, prefix_cache=False, alloc_policy="ondemand",
                 speculate_k=2, observer=obs)
    trace = synthetic_trace(cfg, requests=3, prompt_len=6, gen_len=4,
                            lengths="uniform", seed=3)
    agg = eng.run(trace)
    return eng, obs, agg


def test_engine_emits_spans_per_completed_request(obs_run, tmp_path):
    eng, obs, agg = obs_run
    assert agg["completed"] == 3
    per_rid = validate_chrome_trace(obs.to_chrome(), require_spec=True)
    assert sorted(per_rid) == [0, 1, 2]
    for counts in per_rid.values():
        assert counts["queue"] == 1 and counts["prefill"] >= 1
    path = obs.export(str(tmp_path), tag="unit")
    assert path.endswith(".trace.json")
    validate_chrome_trace(json.loads(open(path).read()),
                          require_spec=True)
    s = obs.summary()
    assert s["prefill_steps"] >= 3
    assert s["spec_steps"] == eng.spec_cycles
    phases = eng.phase_stats()
    bd = obs.time_breakdown(phases, agg["wall_s"])
    assert bd["wall_s"] == agg["wall_s"]
    # the device wait is the summed *.wait spans, the host's share the
    # rest of engine.step, and the three shares split the run's wall
    assert phases["engine.spec.wait"]["count"] == eng.spec_cycles
    assert bd["wait_s"] == pytest.approx(sum(
        v["total_s"] for k, v in phases.items() if k.endswith(".wait")))
    assert bd["host_s"] == pytest.approx(
        phases["engine.step"]["total_s"] - bd["wait_s"])
    assert bd["wait_s"] > 0 and bd["host_s"] > 0
    shares = sum(bd[k] for k in ("wait_share", "host_share",
                                 "outside_share"))
    assert shares == pytest.approx(1.0, abs=0.01)
    # the observer's engine lane carries the phase spans
    lane = {ev[0] for ev in obs.spans.snapshot() if ev[2] == ENGINE_TID}
    assert {"repro.engine.step", "repro.engine.spec",
            "repro.engine.prefill"} <= lane


def test_engine_disabled_observer_is_default(smoke_serving_setup):
    cfg, qcfg, mcfg, params = smoke_serving_setup
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32)
    assert eng.observer is None


def test_preemption_and_abort_events(smoke_serving_setup):
    cfg, qcfg, mcfg, params = smoke_serving_setup
    obs = EngineObserver()
    # a pool too small for both requests' full contexts: ondemand decode
    # growth must preempt under pressure
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32,
                 page_size=4, num_pages=5, prefix_cache=False,
                 alloc_policy="ondemand", observer=obs)
    trace = synthetic_trace(cfg, requests=2, prompt_len=8, gen_len=12,
                            lengths="fixed", seed=0)
    eng.run(trace)
    names = [ev[0] for ev in obs.spans.snapshot()]
    if eng.preemptions:
        assert "preempt" in names
        assert "resume" in names
    # queued abort leaves a terminal marker without any decode span
    obs2 = EngineObserver()
    eng2 = Engine(cfg, qcfg, mcfg, params, num_slots=1, max_len=32,
                  observer=obs2)
    from repro.serving import Request
    eng2.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    eng2.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=4))
    eng2.step()
    eng2.abort(1)
    evs = [ev for ev in obs2.spans.snapshot()
           if ev[0] == "finish" and ev[2] == request_tid(1)]
    assert len(evs) == 1


def test_driver_prometheus_scrape_under_concurrency(smoke_serving_setup):
    """/metrics renders under the driver lock: hammer prom_text() and
    stats() from scrape threads during a live run and require every
    snapshot to parse cleanly with monotone counters."""
    from repro.server.driver import EngineDriver

    cfg, qcfg, mcfg, params = smoke_serving_setup
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32,
                 page_size=4, prefix_cache=False)
    driver = EngineDriver(eng, max_inflight=8).start()
    done = threading.Event()
    errors: list = []
    # one list per scrape thread: monotonicity is a per-scraper property
    # (a Prometheus server polls from one client), so cross-thread
    # interleaving must not enter the comparison
    per_thread: list = [[] for _ in range(3)]

    def scrape(seen):
        while not done.is_set():
            try:
                parsed = parse_prometheus_text(driver.prom_text())
                vals = [v for s, v in
                        parsed["repro_completed_total"]["samples"]]
                seen.append(vals[0])
                st = driver.stats()
                assert st["completed_total"] >= 0
                assert st["inflight"] >= 0
            except Exception as e:  # surfaced after join
                errors.append(e)
                return

    threads = [threading.Thread(target=scrape, args=(seen,))
               for seen in per_thread]
    try:
        for t in threads:
            t.start()
        finished = threading.Semaphore(0)

        def sink(event):
            if event[0] == "finish":
                finished.release()

        rids = [driver.submit([1, 2, 3, 4], 5, sink=sink)
                for _ in range(4)]
        assert all(r is not None for r in rids)
        for _ in rids:
            assert finished.acquire(timeout=60)
    finally:
        done.set()
        for t in threads:
            t.join(timeout=10)
        driver.shutdown()
    assert not errors, errors
    for seen in per_thread:
        assert seen == sorted(seen), \
            "completed_total went backwards across scrapes"
    assert max(seen[-1] for seen in per_thread if seen) == 4
    # the lifetime histograms saw every finished request
    parsed = parse_prometheus_text(driver.prom_text())
    count = [v for s, v in parsed["repro_ttft_seconds"]["samples"]
             if s["__name__"] == "repro_ttft_seconds_count"]
    assert count[0] == 4


def test_driver_health_context(smoke_serving_setup):
    from repro.server.driver import EngineDriver

    cfg, qcfg, mcfg, params = smoke_serving_setup
    eng = Engine(cfg, qcfg, mcfg, params, num_slots=2, max_len=32,
                 page_size=4, prefix_cache=True, alloc_policy="reserve",
                 speculate_k=2, checkpoint_id="unit-ckpt")
    driver = EngineDriver(eng, max_inflight=8).start()
    try:
        h = driver.health()
        assert h["status"] == "ok"
        assert h["arch"] == cfg.name
        assert h["checkpoint_id"] == "unit-ckpt"
        assert h["paged"] and h["alloc_policy"] == "reserve"
        assert h["prefix_cache"] is True
        assert h["spec"]["k"] == 2
        assert h["backend"]
    finally:
        driver.shutdown()
    assert driver.health()["status"] == "stopping"
