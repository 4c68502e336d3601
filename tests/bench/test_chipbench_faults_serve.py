"""The serving cell's check fails its control and a token altered where
it is produced, at a tiny size on the CPU."""
from chipbench_tiny import ROOT, SERVE_CELL, run_tiny, tiny_cell


def test_token_altered_where_produced_fails(monkeypatch):
    from bench.harness import serve
    build = serve.build_engine

    def broken(cfg, params, opts):
        eng = build(cfg, params, opts)
        sample = eng._sample_impl

        def off_by_one(logits, samp, step_offset=None):
            tok = sample(logits, samp, step_offset=step_offset)
            return (tok + 1) % cfg.vocab_size
        eng._sample_impl = off_by_one
        return eng
    monkeypatch.setattr(serve, "build_engine", broken)
    res = run_tiny(SERVE_CELL)
    assert res["correct"] is False
    c = res["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def test_control_four_bit_reference_fails():
    """The control: the reference on a 4-bit grid in the program's place;
    at each served position, the gap of the token it puts first."""
    from bench.harness import runner, serve
    cell = tiny_cell(SERVE_CELL)
    run = serve.ServeRun(cell, 2 ** 34 + 9)
    run.setup()
    run.window(1.5)
    sample = run.check_sample()
    run.free()
    assert sum(len(s["served"]) for s in sample) >= 100
    q = cell.config["serve"]["quant"]
    fmt = (q["bits"], q["gamma"])
    sound = serve.served_gaps(run.words, sample, run.dims, fmt, fmt)
    ctl = serve.served_gaps(run.words, sample, run.dims, fmt, fmt,
                            control=(4, 1))
    limit = runner.limits_of(ROOT, SERVE_CELL)["served_logit_gap"]
    assert max(sound) <= limit
    assert max(ctl) > limit
