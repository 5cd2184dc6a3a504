"""The yardstick's own arithmetic on the CPU: work counts, the traffic
generator, and the reduction from a recorded chip trace to metrics."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import trace, traffic, work
from bench.tests import tiny

DATA = Path(__file__).resolve().parent / "data"


def _config(name):
    return tiny.load(f"configs/{name}.json")


def test_granite_train_param_and_flop_counts():
    c = _config("granite-3-2b-lotion-int4-train")
    # per layer: wq 2048^2 + wk, wv 2048x512 + wo 2048^2 + 3 x 2048x8192
    assert work.layer_matmul_params(c) == 60_817_408
    # 10 layers plus the 49155 x 2048 tied head: the 709M of a step
    assert work.matmul_params(c) == 608_174_080 + 100_669_440
    assert work.all_params(c) == 708_843_520 + 10 * 2 * 2048 + 2048
    # 28 bytes a parameter for the fused LOTION-AdamW update
    assert work.opt_step_bytes(c) == 28 * work.all_params(c)
    assert work.opt_step_bytes(c) == pytest.approx(19.85e9, rel=1e-3)
    # 6 N T plus causal attention 6 L H hd S^2 B /(S/(S+1))
    n_tok = 8 * 2048
    dense = 6 * 708_843_520 * n_tok
    attn = 3 * 4 * 10 * 32 * 64 * (2048 * 2049 / 2) * 8
    assert work.train_step_flops(c, 8, 2048) == pytest.approx(dense + attn)
    assert work.train_step_flops(c, 8, 2048) == pytest.approx(7.38e13,
                                                              rel=5e-3)


def test_granite_serve_decode_counts():
    c = _config("granite-3-2b-rtn-int4-serve")
    assert work.matmul_params(c) == 40 * 60_817_408 + 100_669_440
    # one token at 1000 keys: 2 N + 4 L H hd ctx
    assert work.token_flops(c, 1000) == pytest.approx(
        2 * work.matmul_params(c) + 4 * 40 * 2048 * 1000)
    peak_f, peak_b = 197e12, 819e9
    # one decode pass of 64 rows is bound by the int4 codes it reads
    t = work.wqt_matmul_bound_s(c, 64, 1, peak_f, peak_b)
    codes = 40 * 60_817_408 * 0.5
    assert codes / peak_b < t < 1.3 * codes / peak_b
    # a 256-row prefill chunk is bound by its FLOPs
    t = work.wqt_matmul_bound_s(c, 256, 1, peak_f, peak_b)
    assert t == pytest.approx(2 * 256 * 40 * 60_817_408 / peak_f, rel=1e-6)
    # int4 K and V with a float32 scale per vector, 8 kv heads of 64
    per_tok = 2 * 40 * 8 * (32 + 4)
    t = work.decode_attn_bound_s(c, [1000], peak_f, peak_b)
    assert t == pytest.approx(
        (1000 * per_tok + 2 * 40 * 32 * 64 * 2) / peak_b)


@pytest.mark.parametrize("mix", ["decode-heavy", "prefill-heavy"])
def test_mix_is_deterministic_in_the_seed(mix):
    m = tiny.load(f"mixes/{mix}.json")
    a = traffic.Requests(m, 2**31 + 12345, 49155)
    b = traffic.Requests(m, 2**31 + 12345, 49155)
    c = traffic.Requests(m, 7, 49155)
    ra = [a.next() for _ in range(40)]
    rb = [b.next() for _ in range(40)]
    rc = [c.next() for _ in range(40)]
    assert ra == rb
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rc]
    for r in ra:
        assert all(1 <= t < 49155 for t in r["prompt"])


@pytest.mark.parametrize("mix", ["decode-heavy", "prefill-heavy"])
def test_mix_sizes_match_their_distribution(mix):
    m = tiny.load(f"mixes/{mix}.json")
    n = m["n_sizes"]
    for key in ("prompt", "output"):
        spec = m[key]
        s = traffic.quantile_sizes(spec, n)
        assert s.min() >= spec["min"] and s.max() <= spec["max"]
        # the median of the multiset is the stated median (or its clip)
        med = min(max(spec["median"], spec["min"]), spec["max"])
        assert abs(np.median(s) - med) <= 1
        # heavy tail: the top decile reaches past twice the median, or
        # the clip
        assert np.percentile(s, 95) >= min(1.8 * med, spec["max"])
    # every seed sends the same sizes and gaps in the same order, with
    # its own token ids
    seqs = []
    for seed in (1, 2**31 + 5):
        r = traffic.Requests(m, seed, 49155)
        got = [r.next() for _ in range(n)]
        seqs.append(got)
    for key, f in (("prompt", lambda q: len(q["prompt"])),
                   ("output", lambda q: q["max_new"])):
        got = sorted(f(q) for q in seqs[0])
        assert got == sorted(traffic.quantile_sizes(m[key], n).tolist())
    assert [len(q["prompt"]) for q in seqs[0]] == [
        len(q["prompt"]) for q in seqs[1]]
    assert [q.get("due") for q in seqs[0]] == [q.get("due") for q in seqs[1]]
    assert [q["prompt"] for q in seqs[0]] != [q["prompt"] for q in seqs[1]]
    if m["loop"] == "open":
        dues = [q["due"] for q in seqs[0]]
        assert all(b > a for a, b in zip(dues, dues[1:]))
        rate = n / dues[-1]
        assert rate == pytest.approx(m["rate_per_s"], rel=0.02)


def test_trace_reduction_on_a_recorded_chip_trace():
    """A 200 ms slice of a traced LOTION step on one v5e (the end of the
    update, the gap between steps, the start of the next step)."""
    with gzip.open(DATA / "train_trace_slice.json.gz", "rt") as f:
        fx = json.load(f)
    dev = fx["devices"]["/device:TPU:0"]
    lo, hi = fx["window"]
    got = trace.reduce_events(fx["devices"],
                              [tuple(s) for s in fx["spans"]], (lo, hi))
    # busy: the union of program executions inside the window, by hand
    mods = sorted((max(s, lo), min(s + d, hi)) for _, s, d in dev["modules"]
                  if s < hi and s + d > lo)
    busy, end = 0.0, lo
    for a, b in mods:
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    assert got["busy_s"] == pytest.approx(busy / 1e9)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    # the opt_step kernel: every tpu_custom_call event named *opt_step*
    k = sum(min(s + d, hi) - max(s, lo) for t, s, d in dev["ops"]
            if "tpu_custom_call" in t and "opt_step" in t.split(" = ")[0])
    assert got["kernel_s"]["opt_step"] == pytest.approx(k / 1e9)
    assert set(got["kernel_s"]) == {"opt_step"}
    # self times of nested ops never add up past the window
    assert sum(v for _, v in got["breakdown"]["device_ops"]) <= (
        (hi - lo) / 1e9)
    # the idle gap between the steps falls in the train_step span
    assert got["breakdown"]["idle_gaps"][0][0] == "train_step"
    # and the whole reduction is as recorded with the fixture
    assert json.loads(json.dumps(got)) == fx["expected"]


def test_kernel_names_from_op_text():
    assert trace.kernel_name("%vmap_opt_step_.13 = f32[] custom-call()") \
        == "opt_step"
    assert trace.kernel_name("%wqt_matmul.7 = bf16[8,2048]") == "wqt_matmul"
    assert trace.kernel_name(
        "%decode_attn_paged.2 = f32[]") == "decode_attn_paged"


def test_gap_stats_over_served_tokens():
    from bench.drivers import serve
    t = serve.TAIL_GAP
    st = serve.gap_stats([np.array([0.0, t + 0.1, 0.0]),
                          np.array([t / 2, t])])
    assert st["served_gap_max"] == pytest.approx(t + 0.1)
    assert st["served_tail_share"] == pytest.approx(1 / 5)
    # no token to compare, or a non-finite gap, can never pass
    assert all(v == float("inf") for v in serve.gap_stats([]).values())
    assert all(v == float("inf") for v in serve.gap_stats(
        [np.array([0.0, np.nan])]).values())


def test_memory_peak_holds_the_reserved_program_scratch():
    from bench.harness import common
    # as a v5e reads after a decode-heavy window: the tick's 9.08 GB of
    # temp is held as reserved bytes, outside peak_bytes_in_use
    serving = {"bytes_in_use": 3_615_215_616,
               "peak_bytes_in_use": 3_845_903_360,
               "peak_bytes_reserved": 9_037_217_792}
    assert common.memory_peak([serving]) == 3_615_215_616 + 9_037_217_792
    # a peak in use above the two together stands as it is
    setup = {"bytes_in_use": 10, "peak_bytes_in_use": 500,
             "peak_bytes_reserved": 100}
    assert common.memory_peak([serving, setup]) == common.memory_peak(
        [serving])
    assert common.memory_peak([setup]) == 500
    assert common.memory_peak([{}]) == 0
