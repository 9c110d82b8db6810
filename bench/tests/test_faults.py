"""A run with the timed path broken underneath must come out not
correct; the same run unbroken must come out correct.  The cells run at
a size a test run holds, on the CPU, without the harness's look for a
chip, against limits of their own at that size (`tiny.py`)."""

import pytest

from bench import harness
from bench.tests import tiny

harness.program_on_path()

import repro.models.transformer as transformer  # noqa: E402
import repro.runtime.runner as runner  # noqa: E402


def state_unchanged(mp):
    make = runner.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def same(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return same
    mp.setattr(runner, "make_train_step", broken)


def half_batch(mp):
    xent = transformer.softmax_xent

    def broken(logits, labels, **k):
        n = logits.shape[1] // 2
        return xent(logits[:, :n], labels[:, :n], **k)
    mp.setattr(transformer, "softmax_xent", broken)


def test_sound_run_is_correct():
    out = tiny.run(tiny.cell("granite-train-1chip"))
    assert out.correct, out.compared


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = tiny.run(tiny.cell("granite-train-1chip"))
    assert not out.correct, out.compared


def test_unchanged_state_reads_one():
    """A step that returns its state unchanged moves no parameter and
    leaves Adam's moments at zero: every such leaf reads a gap of 1."""
    from bench.drivers import train
    norms = {"m": {"a": 1.0, "b": 2.0}, "v": {"a": 0.1, "b": 0.2},
             "change": {"a": 0.5, "b": 0.25}}
    still = {k: dict.fromkeys(d, 0.0) for k, d in norms.items()}
    got = train.compare(([1.0], still), ([1.0], norms))
    assert got["update_gap"] == pytest.approx(1.0)
    assert got["m_gap"] == pytest.approx(1.0)
    assert max(train.compare(([1.0], norms), ([1.0], norms)).values()) == 0
