"""Continuous-batching scheduler invariants: greedy parity vs static
batching, scan-vs-per-step decode bit-parity, slot-reuse KV isolation,
FIFO admission fairness, the structural dispatch bound, MoE capacity
masking of dead slots, slot-pool cache sharding, and the chunked+prefix
offered-load replay (stall bound + prefix-skip; chunked-prefill edge
cases live in tests/test_chunked_prefill.py)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.lm import (LMConfig, cache_insert, init_cache, lm_decode,
                             lm_init, lm_prefill)
from repro.serve import Engine, Scheduler, SchedulerConfig, ServeConfig
from repro.serve.replay import (compare, poisson_workload, replay_continuous,
                                replay_static)
from repro.serve.slots import SlotPool

CFG = LMConfig(name="s", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=64, dtype=jnp.float32, remat=False)
PROMPTS = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10], [11, 3], [9, 9, 9]]


def _params():
    return lm_init(jax.random.PRNGKey(0), CFG)


def _sched(params, n_slots=3, k=4, cache_len=64, **scfg_kw):
    return Scheduler(CFG, params, ServeConfig(max_new_tokens=8, **scfg_kw),
                     SchedulerConfig(n_slots=n_slots, steps_per_tick=k,
                                     cache_len=cache_len))


def test_scheduler_greedy_parity_with_static_batching():
    """ISSUE 4 acceptance: greedy generations through the scheduler are
    token-identical to static-batch generate for the same request set —
    ragged prompts, fewer slots than requests, multiple reuse cycles."""
    params = _params()
    want = Engine(CFG, params, ServeConfig(max_new_tokens=8)).generate(PROMPTS)
    got = _sched(params, n_slots=2, k=3).generate(PROMPTS)
    assert got == want


def test_scheduler_parity_quantized_storage_and_kv_cache():
    """Parity holds end-to-end through QTensor int4 weights and the
    quantized KV cache (both engines share the representation)."""
    params = _params()
    for kv in (False, "int8", "int4"):
        scfg = dict(weights="rtn:int4", kv_quant=kv, use_kernel=False)
        want = Engine(CFG, params, ServeConfig(**scfg)
                      ).generate(PROMPTS[:4], max_new_tokens=6)
        got = _sched(params, n_slots=2, k=2, **scfg).generate(
            PROMPTS[:4], max_new_tokens=6)
        assert got == want, kv


def test_scheduler_per_request_budgets_and_eos():
    params = _params()
    eng = Engine(CFG, params, ServeConfig(max_new_tokens=8))
    mnts = [3, 8, 1, 5]
    want = eng.generate(PROMPTS[:4], max_new_tokens=mnts)
    got = _sched(params, n_slots=2, k=3).generate(PROMPTS[:4],
                                                  max_new_tokens=mnts)
    assert got == want
    assert [len(r) for r in got] == mnts
    # EOS: pick a token the greedy stream actually emits mid-generation
    eos = want[1][2]
    w2 = eng.generate(PROMPTS[:4], max_new_tokens=8, eos_id=eos)
    g2 = _sched(params, n_slots=3, k=4).generate(PROMPTS[:4],
                                                 max_new_tokens=8, eos_id=eos)
    assert g2 == w2
    assert g2[1][-1] == eos and len(g2[1]) == 3     # stopped AT the EOS


def test_scan_decode_bit_parity_with_per_step_decode():
    """One k-step tick == k explicit ``lm_decode`` calls on the same pool
    (greedy): identical tokens AND bit-identical KV caches — the lax.scan
    is a dispatch-count optimization, not a numerics change."""
    params = _params()
    sch = _sched(params, n_slots=2, k=4)
    rid = sch.submit(PROMPTS[0], 16)
    sch._admit()                       # prefill-insert, no tick yet
    req = sch.requests[rid]
    cache = jax.tree.map(jnp.copy, sch._cache)
    state = {k2: jnp.copy(v) for k2, v in sch._state.items()}

    sch.step()                         # one 4-step on-device tick
    # manual per-step replica of the tick on the saved pool state
    toks = []
    tok, pos, active = state["tok"], state["pos"], state["active"]
    for _ in range(4):
        pos = jnp.where(active, pos + 1, pos)
        logits, cache = jax.jit(lm_decode, static_argnums=(1,))(
            params, CFG, cache, tok[:, None], pos, token_mask=active)
        tok = jnp.where(active, jnp.argmax(logits[:, 0], -1), tok
                        ).astype(jnp.int32)
        toks.append(int(tok[0]))
    assert req.out[1:] == toks
    for a, b in zip(jax.tree.leaves(sch._cache), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kv_quant", [False, "int8"])
def test_slot_reuse_never_leaks_kv(kv_quant):
    """A request decoded in a reused slot generates exactly what it
    generates alone: the insert replaces the slot's whole cache row and
    the ring-validity mask hides the unwritten tail.  First occupant is
    LONG (fills high cache positions), successor is SHORT — the leakiest
    configuration."""
    params = _params()
    sch = _sched(params, n_slots=1, k=4, kv_quant=kv_quant)
    long_out = sch.generate([[7, 8, 9, 10, 2, 4, 6, 1]],
                            max_new_tokens=24)[0]
    short = [5, 3]
    reused = sch.generate([short], max_new_tokens=8)[0]
    alone = Engine(CFG, params, ServeConfig(max_new_tokens=8,
                                            kv_quant=kv_quant)
                   ).generate([short])[0]
    assert reused == alone
    assert len(long_out) == 24


def test_admission_is_fifo_and_slot_assignment_deterministic():
    params = _params()
    sch = _sched(params, n_slots=2, k=2)
    rids = [sch.submit(p, 4) for p in PROMPTS]
    sch.run()
    reqs = [sch.requests[r] for r in rids]
    # admitted strictly in submit order
    assert [r.admit_seq for r in reqs] == sorted(r.admit_seq for r in reqs)
    # equal budgets: completion cannot invert submission order by more
    # than a slot-width (every admitted request finishes in ceil(3/2)=2
    # ticks, so admission order IS completion order here)
    sch2 = _sched(params, n_slots=2, k=2)
    rids2 = [sch2.submit(p, 4) for p in PROMPTS]
    sch2.run()
    assert [sch.requests[a].out for a in rids] == \
        [sch2.requests[b].out for b in rids2]


def test_dispatch_bound_structural():
    """ISSUE 4 acceptance: decode host->device launches per request <=
    ceil(max_new_tokens / k), verified by counting ticks, at several k."""
    params = _params()
    for k in (1, 2, 4, 8):
        sch = _sched(params, n_slots=3, k=k)
        mnts = [1, 4, 8, 8, 5, 2]
        sch.generate(PROMPTS, max_new_tokens=mnts)
        for rid, mnt in enumerate(mnts):
            assert sch.requests[rid].ticks <= math.ceil(mnt / k), (k, rid)
    # and the batch completes in ~total-work/k ticks, not per-token
    assert sch.n_ticks <= math.ceil(sum(mnts) / 8) + len(mnts)


def test_pad_invariance_not_claimed_for_moe_or_recurrent():
    """attn_only() — the pad-invariance gate — must reject MoE configs
    (pad tokens consume shared expert capacity during prefill, so masking
    attention alone does not decouple batchmates) and recurrent patterns
    (pads advance the state), while accepting dense attention."""
    from repro.serve import attn_only
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
                vocab=64, dtype=jnp.float32, remat=False)
    assert attn_only(LMConfig(name="a", **base))
    assert attn_only(LMConfig(name="l", pattern=("local", "attn"),
                              window=4, **base))
    assert not attn_only(LMConfig(name="m", ffn="moe", n_experts=4,
                                  top_k=2, **base))
    assert not attn_only(LMConfig(name="r", pattern=("rwkv",), **base))


def test_free_slots_do_not_consume_moe_capacity():
    """token_mask: masked (free/retired) slots are excluded from expert
    dispatch — garbage rows must not steal capacity from live requests.
    The live row's decode output is invariant to what the dead rows
    hold."""
    cfg = LMConfig(name="moe", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_ff=64, vocab=64, ffn="moe", n_experts=4,
                   top_k=2, capacity_factor=0.6,   # tight: drops do happen
                   dtype=jnp.float32, remat=False)
    params = lm_init(jax.random.PRNGKey(1), cfg)
    toks = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    _, row = lm_prefill(params, cfg, toks, cache_len=16)
    mask = jnp.asarray([True, False, False, False])
    pos = jnp.zeros((4,), jnp.int32).at[0].set(7)
    outs = []
    for garbage in (0, 17, 63):
        pool = cache_insert(init_cache(cfg, 4, 16, dtype=jnp.float32),
                            row, 0)
        tok = jnp.full((4,), garbage, jnp.int32).at[0].set(11)
        logits, _ = lm_decode(params, cfg, pool, tok[:, None], pos,
                              token_mask=mask)
        outs.append(np.asarray(logits[0]))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_offered_load_replay_continuous_beats_static():
    """The bench's CI assertion, in-suite: same Poisson stream, equal
    slots — identical outputs, per-request dispatch bound, and continuous
    throughput >= static (the static barrier pays max(budget) per group
    and one dispatch per token)."""
    params = _params()
    scfg = ServeConfig(max_new_tokens=16)
    engine = Engine(CFG, params, scfg)
    sch = _sched(params, n_slots=3, k=4, cache_len=32)
    wl = poisson_workload(3, 12, CFG.vocab, rate=200.0, prompt_lens=(2, 6),
                          budgets=(2, 4, 8, 16))
    replay_static(engine, wl, 3)
    replay_continuous(sch, wl)
    rec = compare(replay_static(engine, wl, 3), replay_continuous(sch, wl))
    assert rec["outputs_identical"]
    assert rec["throughput_ratio"] >= 1.0, rec


def test_offered_load_replay_chunked_prefix_parity_and_stall_bound():
    """The ISSUE 5 bench assertion, in-suite: on a chat-shaped stream
    (shared system prompt + a long-prompt straggler) the chunked+prefix
    scheduler matches static outputs exactly, never interposes more than
    one chunk of prefill per tick, and actually skips prefix work."""
    from repro.serve.replay import shared_prefix_workload

    params = _params()
    scfg = ServeConfig(max_new_tokens=16)
    engine = Engine(CFG, params, scfg)
    sch = Scheduler(CFG, params, scfg,
                    SchedulerConfig(n_slots=3, steps_per_tick=4,
                                    cache_len=64, prefill_chunk=4,
                                    prefix_cache=True))
    wl = shared_prefix_workload(5, 10, CFG.vocab, rate=150.0, sys_len=8,
                                straggler_every=5, straggler_len=32,
                                budgets=(2, 4, 8, 16))
    replay_static(engine, wl, 3)
    replay_continuous(sch, wl)
    stat = replay_static(engine, wl, 3)
    cont = replay_continuous(sch, wl)
    rec = compare(stat, cont)
    assert rec["outputs_identical"], rec
    assert rec["continuous"]["prefill_stall_max_tokens"] <= 4
    assert cont["prefill_tokens_skipped"] > 0
    for i, t in cont["ticks"].items():
        assert t <= math.ceil(wl[i].max_new_tokens / 4), (i, t)


def test_scheduler_rejects_oversized_requests():
    sch = _sched(_params(), n_slots=2, k=2, cache_len=16)
    with pytest.raises(ValueError, match="cache_len"):
        sch.submit([1] * 10, max_new_tokens=8)


def test_zero_budget_requests_complete_without_slots():
    sch = _sched(_params(), n_slots=1, k=2)
    assert sch.generate([[1, 2], [3]], max_new_tokens=0) == [[], []]
    assert sch.pool.n_free == 1


def test_slot_pool_bookkeeping():
    pool = SlotPool(3)
    a, b = pool.acquire(10), pool.acquire(11)
    assert (a, b) == (0, 1)            # lowest-free-first
    pool.release(a)
    assert pool.acquire(12) == 0       # reused deterministically
    with pytest.raises(KeyError):
        pool.release(2)
    with pytest.raises(ValueError):
        SlotPool(0)


def test_slot_pool_cache_shardings_cover_scheduler_pool():
    """The slot-pool cache (batch dim = n_slots) and the paged block pool
    (batch dim = n_blocks) flow through the same cache sharding rules as
    static decode — including packed-int4 KV codes (uint8, halved
    trailing dim)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import cache_shardings
    from repro.models.lm import init_paged_pool

    mesh = make_mesh((1, 1), ("data", "model"))
    for kv, paged in [(kv, paged) for kv in (False, "int8", "int4")
                      for paged in (False, True)]:
        init = init_paged_pool if paged else init_cache
        pool = jax.eval_shape(
            lambda kv=kv, init=init: init(CFG, 4, 64, dtype=jnp.float32,
                                          kv_quant=kv))
        sh = cache_shardings(mesh, pool, batch=4)
        leaf = jax.tree_util.tree_leaves_with_path(sh)
        assert leaf                     # every leaf got a sharding
        for path, s in leaf:
            assert isinstance(s.spec, P)
