"""The collectives' self time (``harness/collectives.py``) on synthetic
event lists and on slices of traces recorded on v5e chips: one chip, and
four chips running the 40-layer step on a (data 2, model 2) mesh."""

import gzip
import json
from pathlib import Path

import pytest

from bench.harness import collectives, trace

DATA = Path(__file__).resolve().parent / "data"
WINDOW = (0.0, 1e9)


def _op(name, start, dur):
    """An ``XLA Ops`` event as the trace names it: the op's HLO text."""
    return (f"%{name} = f32[8,128]{{1,0}} {name.split('.')[0]}(...)",
            float(start), float(dur))


def _planes(*per_device):
    return {f"/device:TPU:{i}": {"modules": [], "ops": list(ops)}
            for i, ops in enumerate(per_device)}


def test_every_collective_opcode_and_form_is_counted():
    names = [f"{op}{form}.{i}" for i, (op, form) in enumerate(
        (op, form)
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")
        for form in ("", "-start", "-done"))]
    ops = [_op(n, 100 * i, 10) for i, n in enumerate(names)]
    ops += [_op("fusion.99", 5000, 10), _op("copy-start.1", 6000, 10)]
    got = collectives.collective_s(_planes(ops), WINDOW)
    assert got == pytest.approx(15 * 10 / 1e9)


@pytest.mark.parametrize("text,want", [
    ("%foo.3 = f32[8]{0:T(128)} all-reduce(f32[8]{0:T(128)} %p), "
     "replica_groups={{0,1}}, to_apply=%add", True),
    ("%all-gather-start.1 = (f32[8]{0:T(128)}, f32[16]{0:T(128)S(1)}) "
     "all-gather-start(f32[8]{0:T(128)} %p), dimensions={0}", True),
    ("%reduce-scatter-start.2 = ((f32[8]{0}), f32[4]{0}, s32[]{:S(2)}) "
     "async-start(f32[8]{0} %p), calls=%reduce_scatter.2", True),
    ("%all-gather.9 = bf16[4,2048]{1,0:T(8,128)(2,1)} fusion(bf16[2,2048]"
     "{1,0:T(8,128)(2,1)} %p), kind=kOutput, calls=%fused_computation.9",
     True),
    ("%slice-start.39 = ((bf16[4,2048,1024]{2,1,0:T(8,128)(2,1)}), "
     "bf16[1,2048,1024]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) "
     "async-start(bf16[4,2048,1024]{2,1,0:T(8,128)(2,1)} %copy-done.7), "
     "calls=%async_computation.39", False),
    ("%all-reduce_fusion.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)",
     False),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2), kind=kLoop",
     False),
])
def test_a_collective_is_known_by_its_opcode(text, want):
    """The opcode decides, whatever the instruction is named; a fusion or
    an async wrapper, whose collective sits in a called computation, by
    the collective it is named after."""
    assert collectives.is_collective(text) is want


def test_a_nested_collective_is_taken_out_of_its_parent():
    """A loop's body holds an all-gather: the loop's self time is not a
    collective's, the all-gather's own time is."""
    ops = [_op("while.1", 0, 100), _op("all-gather.2", 10, 20),
           _op("fusion.3", 30, 30)]
    got = collectives.collective_s(_planes(ops), WINDOW)
    assert got == pytest.approx(20 / 1e9)


def test_an_async_pair_counts_its_start_and_the_wait_in_its_done():
    """The compute between ``-start`` and ``-done`` hides the transfer;
    what is left is the start's issue and the done's wait."""
    ops = [_op("all-reduce-start.1", 100, 2), _op("fusion.4", 102, 48),
           _op("all-reduce-done.1", 150, 20)]
    got = collectives.collective_s(_planes(ops), WINDOW)
    assert got == pytest.approx(22 / 1e9)


def test_devices_are_averaged():
    a = [_op("all-reduce.1", 0, 40), _op("fusion.1", 40, 60)]
    b = [_op("all-reduce.1", 0, 20), _op("fusion.1", 20, 80)]
    got = collectives.collective_s(_planes(a, b), WINDOW)
    assert got == pytest.approx(30 / 1e9)


def test_events_are_clipped_at_the_window():
    ops = [_op("all-reduce.1", 90, 30), _op("all-gather.2", 180, 40),
           _op("all-to-all.3", 300, 10)]
    got = collectives.collective_s(_planes(ops), (100.0, 200.0))
    assert got == pytest.approx((20 + 20) / 1e9)


def _slice(name):
    with gzip.open(DATA / name, "rt") as f:
        rec = json.load(f)
    return ({k: {"modules": v["modules"],
                 "ops": [tuple(o) for o in v["ops"]]}
             for k, v in rec["devices"].items()}, tuple(rec["window"]))


def test_one_chip_trace_slice_has_no_collectives():
    """A 200 ms slice of a traced one-chip LOTION step (the yardstick's
    own recorded slice): no collective, so 0."""
    devices, window = _slice("train_trace_slice.json.gz")
    assert devices[next(iter(devices))]["ops"]
    assert collectives.collective_s(devices, window) == 0.0


def test_four_chip_trace_slice_reads_its_collectives():
    """A 260 ms slice of the 40-layer LOTION step traced on four v5e
    chips (data 2, model 2): every chip runs all-gathers, all-reduces,
    all-to-alls and collective-permutes, each op's opcode parses, the
    compiler named every collective after its opcode, and the average
    self time is the slice's recorded reading."""
    devices, window = _slice("train_mesh_trace_slice.json.gz")
    assert len(devices) == 4
    for ev in devices.values():
        ops = {collectives.opcode(t) for t, _, _ in ev["ops"]}
        assert "" not in ops
        assert {"all-gather", "all-reduce", "all-to-all",
                "collective-permute-start", "collective-permute-done",
                "fusion", "while", "async-start"} <= ops
        for t, _, _ in ev["ops"]:
            assert collectives.is_collective(t) is (
                trace.op_label(t) in collectives.COLLECTIVES)
    got = collectives.collective_s(devices, window)
    assert got == pytest.approx(0.044063864, rel=1e-9)
    assert 0 < got < (window[1] - window[0]) / 1e9


def test_mesh_capture_adds_collective_time_to_the_summary():
    """The capture's summary is ``trace.Capture``'s plus ``collective_s``
    (a CPU trace has no device plane, so both device numbers are 0)."""
    import jax.numpy as jnp

    spans = trace.Spans()
    cap = collectives.MeshCapture(True, spans)
    cap.start()
    with spans.span("train_step"):
        jnp.ones((64, 64)).sum().block_until_ready()
    cap.stop()
    got = cap.finish()
    assert got["collective_s"] == 0.0
    assert got["busy_s"] == 0.0 and got["window_s"] > 0
    assert {"kernel_s", "breakdown"} <= set(got)
    assert cap.finish() is got
