"""The training cell's check fails its control and every fault the cell
can have, at a tiny size on the CPU: the harness runs as on the chip (its
look for a chip skipped) with the timed step broken underneath."""
import pytest

from chipbench_tiny import TRAIN_CELL, run_tiny


@pytest.fixture
def broken_step(monkeypatch):
    from bench.harness import train

    def use(builder):
        monkeypatch.setattr(train, "build_step", builder)
    return use


def unchanged_state(cfg, qcfg, mcfg):
    """A step that computes the loss but returns its state unchanged."""
    from repro.training.steps import build_train_step
    step = build_train_step(cfg, qcfg, mcfg)

    def broken(state, batch):
        return state, step(state, batch)[1]
    return broken


def half_batch(cfg, qcfg, mcfg):
    """A step that leaves out half of the batch, its loss the mean over the
    rest."""
    from repro.training.steps import build_train_step
    step = build_train_step(cfg, qcfg, mcfg)

    def broken(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return broken


def failed_checks(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


def test_sound_step_passes():
    res = run_tiny(TRAIN_CELL, seconds=0.3)
    assert res["correct"] is True, res["checks"]


def test_state_left_unchanged_fails(broken_step):
    broken_step(unchanged_state)
    res = run_tiny(TRAIN_CELL, seconds=0.3)
    assert res["correct"] is False
    assert "change_norm_gap" in failed_checks(res)
    assert res["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_fails(broken_step):
    broken_step(half_batch)
    res = run_tiny(TRAIN_CELL, seconds=0.3)
    assert res["correct"] is False, res["checks"]


def test_control_eight_bit_update_words_fails():
    """The control: the program's own path with 8-bit update words where
    the configuration states 16."""
    res = run_tiny(TRAIN_CELL, seconds=0.3, update_bits=8)
    assert res["correct"] is False
    assert "change_norm_gap" in failed_checks(res)
