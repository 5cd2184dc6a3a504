"""The reduction from the program's scopes and spans on a trace to its
numbers (``harness/program_trace.py``): on synthetic event lists, and on
a slice of a traced decode-heavy window recorded on a v5e."""

import gzip
import json
from pathlib import Path

import pytest

from bench.harness import program_trace as pt

DATA = Path(__file__).parent / "data"
MS = 1e6                                   # ns


@pytest.mark.parametrize("stack,want", [
    ("", ((), False)),
    ("jit(_tick_paged_fn)/while/body/kv_cache/dynamic_slice",
     (("kv_cache",), False)),
    ("jit(f)/attn/kv_cache/jit(_where)/select_n", (("attn", "kv_cache"),
                                                   False)),
    ("jit(step)/forward_backward/transpose(jvp())/while/body/mlp/mul",
     (("forward_backward", "mlp"), True)),
    ("jit(step)/forward_backward/transpose(jvp(head))/dot_general",
     (("forward_backward", "head"), True)),
    ("jit(step)/update/vmap(opt_step)/pallas_call", (("update",), False)),
    # a function named like a scope is not one
    ("jit(step)/jit(update)/add", ((), False)),
])
def test_scope_path(stack, want):
    assert pt.scope_path(stack) == want


def _one_device(ops, modules):
    return {"/device:TPU:0": {"modules": modules, "ops": ops}}


def test_scope_self_time_and_shares():
    """A loop's self time leaves out its body; each op counts under its
    scope path, backward apart; shares are over busy time."""
    ops = [("jit(t)/forward_backward/jvp()/while", 0, 60 * MS),
           ("jit(t)/forward_backward/jvp()/while/body/attn/dot_general",
            5 * MS, 20 * MS),
           ("jit(t)/forward_backward/transpose(jvp())/while/body/mlp/mul",
            30 * MS, 20 * MS),
           ("jit(t)/update/vmap(opt_step)/pallas_call", 60 * MS, 30 * MS),
           ("", 90 * MS, 5 * MS)]
    got = pt.reduce_program(_one_device(ops, [("jit_t", 0, 95 * MS)]), [],
                            (0, 100 * MS))
    assert got["busy_s"] == pytest.approx(0.095)
    assert got["scope_s"] == pytest.approx({
        "forward_backward": 0.02, "forward_backward/attn": 0.02,
        "forward_backward/mlp (backward)": 0.02, "update": 0.03,
        "(no scope)": 0.005})
    assert got["update_share"] == pytest.approx(100 * 30 / 95)
    assert got["kv_cache_share"] == 0.0
    assert got["unscoped_share"] == pytest.approx(100 * 5 / 95)
    assert got["idle_by_span"] == pytest.approx({"no program span": 0.005})


def test_nested_scopes_count_under_each():
    """An op under ``attn/kv_cache`` counts toward the ``kv_cache`` share
    (and is labelled with its whole path)."""
    ops = [("jit(d)/while/body/attn/kv_cache/gather", 0, 10 * MS),
           ("jit(d)/while/body/kv_cache/dynamic_slice", 10 * MS, 30 * MS),
           ("jit(d)/while/body/attn/pallas_call", 40 * MS, 60 * MS)]
    got = pt.reduce_program(_one_device(ops, [("jit_d", 0, 100 * MS)]), [],
                            (0, 100 * MS))
    assert got["kv_cache_share"] == pytest.approx(40.0)
    assert set(got["scope_s"]) == {"attn/kv_cache", "kv_cache", "attn"}


def test_host_time_per_step_and_idle_by_innermost_span():
    """Host time per step is each step that ended in the window less its
    syncs; each idle piece goes to the innermost span open over it."""
    host = [("serve.step", 0, 100 * MS, {}),
            ("serve.decode_tick", 10 * MS, 40 * MS, {"slots": 4}),
            ("serve.sync", 40 * MS, 70 * MS, {}),
            ("serve.step", 100 * MS, 110 * MS, {}),
            ("serve.retire", 102 * MS, 108 * MS, {}),
            ("serve.step", 112 * MS, 200 * MS, {})]     # ends after it
    got = pt.reduce_program(
        _one_device([], [("jit_tick", 0, 95 * MS)]), host, (0, 120 * MS))
    assert got["host_ms_per_step"] == pytest.approx(
        {"serve.step": ((100 - 30) + 10) / 2})
    assert got["idle_by_span"] == pytest.approx({
        "serve.step": 0.005 + 0.002 + 0.002 + 0.008,
        "serve.retire": 0.006, "no program span": 0.002})


def test_recorded_decode_slice():
    """A slice of a traced decode-heavy window on one v5e (this program's
    scopes and spans): the reduction reads what it read when recorded."""
    with gzip.open(DATA / "decode_program_slice.json.gz", "rt") as f:
        d = json.load(f)
    devices = {k: {"modules": [tuple(e) for e in v["modules"]],
                   "ops": [tuple(e) for e in v["ops"]]}
               for k, v in d["devices"].items()}
    host = [(n, s, e, st) for n, s, e, st in d["host"]]
    got = pt.reduce_program(devices, host, tuple(d["window"]))
    want = d["expected"]
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k
    assert got["kv_cache_share"] > 0 and got["host_ms_per_step"]
