"""The serving cells' runs at a CPU size: sound runs are correct, a
token altered where it is produced turns ``correct`` false, and the
int8-activation control reads above a sound run."""

import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny


def _run(monkeypatch, capsys, cell, variant=None, trace=0):
    tiny.patch(monkeypatch, cell)
    rc = run.main(["--workload", cell, "--seed", "2147483701",
                   "--seconds", "1", "--trace", str(trace)],
                  device_check=False, variant=variant)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["serve.granite-3-2b.decode-heavy",
                                  "serve.granite-3-2b.prefill-heavy"])
def test_sound_run_is_correct(monkeypatch, capsys, cell):
    out = _run(monkeypatch, capsys, cell)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in tiny.plan(cell)["end_to_end"]}


def test_altered_token_is_caught(monkeypatch, capsys):
    from repro.serve import scheduler as sc
    init = sc.Scheduler.__init__

    def altered(self, *a, **k):
        init(self, *a, **k)
        tick, vocab = self._tick, self.cfg.vocab

        def bad_tick(*args):
            pool, state, em, bad = tick(*args)
            return pool, state, jnp.where(em >= 0, (em + 1) % vocab, em), bad
        self._tick = bad_tick
    monkeypatch.setattr(sc.Scheduler, "__init__", altered)
    out = _run(monkeypatch, capsys, "serve.granite-3-2b.decode-heavy")
    assert out["correct"] is False, out["compared"]


def test_control_reads_above_a_sound_run(monkeypatch, capsys):
    cell = "serve.granite-3-2b.decode-heavy"
    sound = _run(monkeypatch, capsys, cell)["compared"]["served_gap_max"]
    ctl = _run(monkeypatch, capsys, cell, "control")["compared"]
    assert ctl["served_gap_max"]["value"] > sound["value"]
