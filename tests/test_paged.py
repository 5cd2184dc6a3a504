"""Paged KV tests (ISSUE 10): kernel-vs-oracle parity on scattered
block tables, scheduler token-identity vs the dense ring across KV
formats and admission modes, zero-copy prefix sharing, exact reattach
resume after preemption, typed pool-exhaustion rejection, chaos replay
with block audits, and the check_regression --bench filter."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import (decode_attn_paged,
                                       decode_attn_paged_ref,
                                       decode_attn_ref)
from repro.models.layers import attn_decode_paged, kv_quantize
from repro.models.lm import LMConfig, init_paged_pool, lm_init
from repro.serve import (REJECTED, Scheduler, SchedulerConfig,
                         ServeConfig, chaos_plan, check_drained)
from repro.serve.replay import replay_chaos, sla_workload

CFG = LMConfig(name="pg", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=128, dtype=jnp.float32, remat=False)
PARAMS = lm_init(jax.random.PRNGKey(0), CFG)

B, BPS, BS, G, HD = 4, 4, 16, 2, 64
POS = (5, 63, 150, 0)       # partial, exactly-full, ring-wrapped, idle
R, LAYER = 3, 1             # stacked pool; the middle layer is read


def _paged_kv(seed, bits):
    """Dense quantized ring KV of rows 0-2, and the same bytes at layer
    ``LAYER`` of a stacked pool in the kernel's layout (codes (R, nb, bs,
    G*hd_c), scales (R, nb, G, bs)) behind shuffled block tables; the
    other layers and the null block hold noise, and row 3 is an idle slot
    whose table is all null block.  Returns (dense codes, dense scale,
    pool codes, pool scale, tables)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, BPS * BS, G, HD),
                          jnp.float32)
    q = kv_quantize(x, bits)
    codes, scale = np.asarray(q["codes"]), np.asarray(q["scale"])
    nb = 1 + (B - 1) * BPS
    rng = np.random.default_rng(seed)
    tables = np.zeros((B, BPS), np.int32)
    tables[:B - 1] = rng.permutation(np.arange(1, nb)).reshape(B - 1, BPS)
    noise = kv_quantize(jax.random.normal(
        jax.random.PRNGKey(seed + 1), (R * nb, BS, G, HD)), bits)
    pc = np.asarray(noise["codes"]).reshape(R, nb, BS, -1).copy()
    ps = np.swapaxes(np.asarray(noise["scale"])[..., 0].reshape(
        R, nb, BS, G), 2, 3).copy()
    cb = codes.reshape(B, BPS, BS, -1)
    sb = np.swapaxes(scale[..., 0].reshape(B, BPS, BS, G), 2, 3)
    for i in range(B - 1):
        for j in range(BPS):
            pc[LAYER, tables[i, j]] = cb[i, j]
            ps[LAYER, tables[i, j]] = sb[i, j]
    return (jnp.asarray(codes), jnp.asarray(scale),
            jnp.asarray(pc), jnp.asarray(ps), jnp.asarray(tables))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_paged_kernel_matches_oracle(bits, window, softcap):
    kc, ks, kcp, ksp, tables = _paged_kv(1, bits)
    vc, vs, vcp, vsp, _ = _paged_kv(1, bits)   # same tables by seed
    q = jax.random.normal(jax.random.PRNGKey(3), (B, G, 2, HD),
                          jnp.float32)
    pos = jnp.asarray(POS, jnp.int32)
    got = decode_attn_paged(q, kcp, ksp, vcp, vsp, tables, pos, LAYER,
                            bits=bits, window=window, softcap=softcap)
    want = decode_attn_paged_ref(q, kcp, ksp, vcp, vsp, tables, pos, LAYER,
                                 bits=bits, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # the paged oracle over a scattered pool IS the dense-ring oracle
    n = B - 1
    dense = decode_attn_ref(q[:n], kc[:n], ks[:n], vc[:n], vs[:n], pos[:n],
                            bits=bits, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(want[:n]), np.asarray(dense),
                               atol=1e-6, rtol=1e-6)


def test_paged_kernel_rejects_unstacked_layout():
    """The kernel names the stored layout it expects."""
    _, _, kcp, ksp, tables = _paged_kv(1, 4)
    q = jnp.zeros((B, G, 2, HD), jnp.float32)
    pos = jnp.asarray(POS, jnp.int32)
    old = ksp[LAYER][:, :, :, None].swapaxes(1, 2)   # (nb, bs, G, 1)
    with pytest.raises(ValueError, match=r"k_scale shape .* != \(3, 13, 2, 16\)"):
        decode_attn_paged(q, kcp, old, kcp, ksp, tables, pos, LAYER, bits=4)


@pytest.mark.parametrize("kvq", [False, "int8", "int4"])
def test_paged_write_touches_one_token_per_row(kvq):
    """Decoding layer ``r`` writes each row's new token at (r, block,
    offset) of the stacked pool and leaves every other byte — the other
    layers included — unchanged."""
    spec = CFG.attn_spec("attn")
    pool = init_paged_pool(CFG, 9, 8, dtype=jnp.float32, kv_quant=kvq)
    pool = jax.tree.map(lambda a: (jax.random.uniform(
        jax.random.PRNGKey(a.ndim), a.shape) * 100).astype(a.dtype), pool)
    leaf = pool["unit"]["b0_attn"]
    tables = jnp.asarray([[3, 1, 4, 2], [5, 6, 7, 8]], jnp.int32)
    pos = jnp.asarray([13, 30], jnp.int32)    # blocks 1 and 3, offsets 5, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 1, CFG.d_model))
    p = jax.tree.map(lambda a: a[0], PARAMS["stage"]["b0_attn"]["attn"])
    _, nk, nv = attn_decode_paged(p, spec, x, pos, leaf["k"], leaf["v"],
                                  tables, jnp.int32(1))
    for old, new in ((leaf["k"], nk), (leaf["v"], nv)):
        for name, a, b in zip(("codes", "scale"), jax.tree.leaves(old),
                              jax.tree.leaves(new)):
            a, b = np.asarray(a), np.asarray(b)
            tok = 3 if (kvq and name == "scale") else 2
            changed = np.argwhere(np.any(
                a != b, axis=tuple(d for d in range(a.ndim)
                                   if d not in (0, 1, tok))))
            assert {tuple(c) for c in changed} == {(1, 1, 5), (1, 8, 6)}


# --------------------------------------------------------------------------
# scheduler parity: paged pool vs dense ring, token-identical
# --------------------------------------------------------------------------

def _prompts(rng, n, shared_len=16, tail=(2, 6)):
    shared = [int(x) for x in rng.integers(1, CFG.vocab, shared_len)]
    return [shared + [int(x) for x in
                      rng.integers(1, CFG.vocab, int(t))]
            for t in rng.integers(tail[0], tail[1], n)]


@pytest.mark.parametrize("kvq", [False, "int8", "int4"])
@pytest.mark.parametrize("chunked", [False, True])
def test_scheduler_paged_matches_ring(kvq, chunked):
    scfg = ServeConfig(weights="fp32", kv_quant=kvq, max_new_tokens=6)
    kw = dict(n_slots=2, steps_per_tick=2, cache_len=32)
    if chunked:
        kw.update(prefill_chunk=8, prefix_cache=True,
                  prefix_cache_blocks=16)
    prompts = _prompts(np.random.default_rng(5), 6)
    ring = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(**kw))
    want = ring.generate(prompts, 6)
    paged = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(
        paged=True, block_size=8, **kw))
    got = paged.generate(prompts, 6)
    assert got == want
    assert paged.splice_host_transfers == 0
    if chunked:
        # prefix hits are block-table appends, never row copies
        assert paged.prefix_blocks_shared >= 1
        assert ring.splice_host_transfers >= 1
    assert not [p for p in check_drained(paged)]


def test_paged_reattach_exact_after_preemption():
    """A preempted DECODING victim keeps its quantized blocks and
    resumes by table reattach: token-identical to the never-preempted
    run with zero recomputed tokens — for int4 KV, where the legacy
    recompute-resume is inexact (PR 7 gap)."""
    scfg = ServeConfig(weights="fp32", kv_quant="int4", max_new_tokens=10)
    kw = dict(n_slots=1, steps_per_tick=2, cache_len=32, paged=True,
              block_size=8, pool_blocks=9)   # room for victim + preemptor
    rng = np.random.default_rng(7)
    lo = [int(x) for x in rng.integers(1, CFG.vocab, 8)]
    hi = [int(x) for x in rng.integers(1, CFG.vocab, 4)]

    alone = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(**kw))
    r0 = alone.submit(lo, 10)
    alone.run()

    pre = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(**kw))
    r1 = pre.submit(lo, 10, priority=0)
    for _ in range(2):
        pre.step()
    pre.submit(hi, 4, priority=5)
    pre.run()

    assert pre.counters["preempted"] >= 1
    assert pre.requests[r1].out == alone.requests[r0].out
    assert pre.resume_recompute_tokens == 0
    assert pre.resume_splice_tokens >= len(lo)
    assert not [p for p in check_drained(pre)]


def test_pool_exhaustion_typed_rejection_and_recovery():
    """With every block externally held (free < blocks-per-context and
    nothing reclaimable), admission terminates the request REJECTED
    with the typed ``pool_exhausted`` reason instead of livelocking;
    freeing the blocks restores normal admission."""
    scfg = ServeConfig(weights="fp32", max_new_tokens=4)
    sch = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(
        n_slots=1, steps_per_tick=2, cache_len=32, paged=True,
        block_size=8))
    held = sch.block_pool.alloc(sch.block_pool.n_free)
    rid = sch.submit([1, 2, 3], 4)
    while sch.has_work():
        sch.step()
    req = sch.requests[rid]
    assert req.state == REJECTED and req.finish_reason == "pool_exhausted"
    for bid in held:
        sch.block_pool.unref(bid)
    rid2 = sch.submit([1, 2, 3], 4)
    sch.run()
    assert len(sch.requests[rid2].out) == 4
    assert not [p for p in check_drained(sch)]


def test_paged_chaos_replay_clean():
    """Seeded fault replay over the paged pool + prefix trie: the
    per-tick block audits (refcount balance, free/live exclusivity)
    and the drain leak checks must stay silent."""
    scfg = ServeConfig(weights="fp32", max_new_tokens=6)
    sch = Scheduler(CFG, PARAMS, scfg, SchedulerConfig(
        n_slots=2, steps_per_tick=2, cache_len=32, prefill_chunk=8,
        prefix_cache=True, prefix_cache_blocks=16, paged=True,
        block_size=8, max_queue=8, est_tok_per_s=200.0))
    wl = sla_workload(3, 10, CFG.vocab, rate=60.0, prompt_lens=(2, 12),
                      deadline_frac=0.4, slack=(2.0, 10.0),
                      hi_priority_frac=0.3)
    plan = chaos_plan(seed=3, n_ticks=64, vocab=CFG.vocab,
                      cache_len=32, nan_rate=0.2)
    res = replay_chaos(sch, wl, plan=plan, tick_s=0.05)
    assert res["violations"] == []
    assert sum(res["by_state"].values()) == 10


# --------------------------------------------------------------------------
# check_regression --bench filter
# --------------------------------------------------------------------------

_OPT_REC = {"structural": {
    "fused_passes_per_leaf": 3, "unfused_passes_per_leaf": 8,
    "eliminated_passes_per_leaf": 5, "leaf_shape": [64, 64],
    "n_leaves": 4,
    "fused_kernel_contract": {"kernel_calls": 1, "kernel_reads": 4,
                              "kernel_writes": 3, "extra_passes": 0}}}


def test_check_regression_bench_filter(tmp_path):
    from benchmarks import check_regression as cr
    fresh, base = tmp_path / "fresh", tmp_path / "base"
    fresh.mkdir(), base.mkdir()
    for d in (fresh, base):
        (d / "BENCH_opt_step.json").write_text(json.dumps(_OPT_REC))
    # --bench restricts the gate to the named bench: serve/train fresh
    # files are absent but must not be required
    assert cr.main(["--fresh-dir", str(fresh), "--baseline-dir",
                    str(base), "--bench", "opt_step"]) == 0
    # without the filter every declared bench is required
    assert cr.main(["--fresh-dir", str(fresh),
                    "--baseline-dir", str(base)]) == 1
    # the filtered gate still detects regressions in its bench
    worse = json.loads(json.dumps(_OPT_REC))
    worse["structural"]["fused_passes_per_leaf"] = 4
    (fresh / "BENCH_opt_step.json").write_text(json.dumps(worse))
    assert cr.main(["--fresh-dir", str(fresh), "--baseline-dir",
                    str(base), "--bench", "opt_step"]) == 1
