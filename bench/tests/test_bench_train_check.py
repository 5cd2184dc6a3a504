"""The training cell's run at a CPU size: a sound run is correct, and
each fault planted under the timed path turns ``correct`` false."""

import json

import pytest

from bench import run
from bench.tests import tiny

CELL = "train.granite-3-2b.b8x2048"


def _run(monkeypatch, capsys, variant=None):
    tiny.patch(monkeypatch, CELL)
    rc = run.main(["--workload", CELL, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"], device_check=False,
                  variant=variant)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out


def test_sound_run_is_correct(monkeypatch, capsys):
    out = _run(monkeypatch, capsys)
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"


def test_state_left_unchanged_is_caught(monkeypatch, capsys):
    import repro.train as rt
    make = rt.make_train_step

    def frozen(*a, **k):
        step = make(*a, **k)

        def same_state(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return same_state
    monkeypatch.setattr(rt, "make_train_step", frozen)
    out = _run(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["compared"]["change_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_is_caught(monkeypatch, capsys):
    out = _run(monkeypatch, capsys, variant="half_batch")
    assert out["correct"] is False, out["compared"]


def test_control_reads_far_above_a_sound_run(monkeypatch, capsys):
    """The control: the reference with every matmul operand rounded to
    float8, one step of precision below the configuration's, in the
    program's place.  At this size the limits are not calibrated, so the
    test holds the separation, not the limit."""
    sound = _run(monkeypatch, capsys)["compared"]
    ctl = _run(monkeypatch, capsys, variant="control")["compared"]
    assert max(ctl[k]["value"] / max(sound[k]["value"], 1e-12)
               for k in ctl) > 100
