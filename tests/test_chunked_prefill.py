"""Chunked prefill + prefix-cache invariants (DESIGN.md §8): greedy
token-parity of chunked-vs-monolithic prefill (incl. QTensor int4
weights and the int4 KV cache), chunk-size edge cases (prompt shorter
than one chunk, exact chunk multiples), decode-stall bounding, prefix
hits that skip work without changing outputs, eviction mid-flight, and
the trie's refcount/LRU bookkeeping."""

import math

import jax
import jax.numpy as jnp
import pytest

from repro.models.lm import LMConfig, lm_init
from repro.serve import (Engine, PrefixCache, Scheduler, SchedulerConfig,
                         ServeConfig)
from repro.serve.slots import ACTIVE, PREFILLING

CFG = LMConfig(name="c", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=64, dtype=jnp.float32, remat=False)
# covers: shorter than every chunk size, exactly one chunk (7), an exact
# chunk multiple (14 = 2x7), and lengths straddling chunk boundaries
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [6],
           [7, 8, 9, 10, 2, 4, 6, 1, 3, 5, 11, 12, 13, 14], [11, 3]]


def _params():
    return lm_init(jax.random.PRNGKey(0), CFG)


def _sched(params, chunk, prefix=False, n_slots=2, k=3, cache_len=64,
           blocks=256, **scfg_kw):
    return Scheduler(CFG, params, ServeConfig(max_new_tokens=8, **scfg_kw),
                     SchedulerConfig(n_slots=n_slots, steps_per_tick=k,
                                     cache_len=cache_len,
                                     prefill_chunk=chunk,
                                     prefix_cache=prefix,
                                     prefix_cache_blocks=blocks))


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_chunked_prefill_greedy_parity(chunk):
    """ISSUE 5 acceptance: scheduler greedy outputs with chunked prefill
    (and the prefix cache on) are token-identical to the static engine —
    at chunk widths below, at, and far above every prompt length."""
    params = _params()
    want = Engine(CFG, params, ServeConfig(max_new_tokens=8)).generate(PROMPTS)
    for prefix in (False, True):
        got = _sched(params, chunk, prefix=prefix).generate(PROMPTS)
        assert got == want, (chunk, prefix)


def test_chunked_parity_quantized_storage_and_kv_cache():
    """Parity holds through QTensor int4 weights + int4 KV: the partial
    cache stays dense across chunks and quantizes once at insert."""
    params = _params()
    for kv in ("int8", "int4"):
        scfg = dict(weights="rtn:int4", kv_quant=kv, use_kernel=False)
        want = Engine(CFG, params, ServeConfig(**scfg)
                      ).generate(PROMPTS[:4], max_new_tokens=6)
        got = _sched(params, 4, prefix=True, **scfg).generate(
            PROMPTS[:4], max_new_tokens=6)
        assert got == want, kv


def test_prefix_cache_hits_skip_work_and_keep_outputs():
    """Requests sharing a system prompt: later admissions splice the
    shared chunks from the trie (tokens skipped > 0) and still generate
    exactly what the static engine generates."""
    params = _params()
    sys_p = [7, 3, 9, 1, 4, 4, 2, 8]
    prompts = [sys_p + [i + 1, i + 2] for i in range(5)]
    want = Engine(CFG, params, ServeConfig(max_new_tokens=6)).generate(prompts)
    sch = _sched(params, 4, prefix=True, cache_len=32)
    # sequential submits: the first request's publish precedes the rest
    outs = [sch.generate([p], max_new_tokens=6)[0] for p in prompts]
    assert outs == want
    assert sch.prefill_tokens_skipped >= 4 * len(sys_p)
    assert sch.prefix.stats()["hits"] >= 4
    # one BURST on a cold trie: all requests admitted together must still
    # hit — the lookup is deferred to prefill start, so sharers see the
    # chunks the first sharer publishes mid-flight
    sch2 = _sched(params, 4, prefix=True, cache_len=32, n_slots=2)
    assert sch2.generate(prompts, max_new_tokens=6) == want
    assert sch2.prefill_tokens_skipped >= 4 * len(sys_p)


def test_prefix_cache_eviction_mid_flight():
    """A hit whose blocks are LRU-evicted right after the splice (tiny
    capacity + competing prefixes) must not corrupt the consumer: the
    splice is a copy, and pinned nodes are not evictable while the
    consumer is still prefilling."""
    params = _params()
    sys_p = [7, 3, 9, 1]
    prompts = ([sys_p + [i + 1] for i in range(3)]
               + [[i + 9] * 6 for i in range(3)]       # evictor prefixes
               + [sys_p + [50]])                       # re-miss or re-hit
    want = Engine(CFG, params, ServeConfig(max_new_tokens=5)).generate(prompts)
    sch = _sched(params, 2, prefix=True, cache_len=32, blocks=2)
    outs = [sch.generate([p], max_new_tokens=5)[0] for p in prompts]
    assert outs == want
    stats = sch.prefix.stats()
    assert stats["evictions"] > 0
    assert stats["blocks"] <= 2


def test_decode_not_stalled_by_long_prompt():
    """The head-of-line fix itself: while a 32-token prompt drips in at 2
    tokens/tick, an already-active request keeps emitting tokens every
    tick — and no tick ever interposes more than one chunk of prefill."""
    params = _params()
    sch = _sched(params, 2, n_slots=2, k=2, cache_len=64)
    stall = []                         # prefill tokens computed per step

    def step():
        computed = sch.prefill_tokens_computed
        sch.step()
        stall.append(sch.prefill_tokens_computed - computed)

    short = sch.submit([5, 3], max_new_tokens=16)
    for _ in range(3):
        step()                         # short is prefilled + decoding
    assert sch.requests[short].state == ACTIVE
    long_r = sch.submit(list(range(1, 33)), max_new_tokens=4)
    grew = 0
    while not (sch.requests[long_r].state == ACTIVE
               or sch.requests[long_r].done):
        before = len(sch.requests[short].out)
        step()
        assert sch.requests[long_r].state in (PREFILLING, ACTIVE)
        grew += len(sch.requests[short].out) > before
    assert grew >= 5                   # decode progressed during prefill
    while sch.has_work():
        step()
    assert max(stall) <= 2             # never more than one chunk per tick
    # and the decode dispatch bound still holds for every request
    for rid, req in sch.requests.items():
        assert req.ticks <= math.ceil(req.max_new_tokens / 2), rid


def test_chunked_monolithic_same_outputs_any_interleaving():
    """Chunked and monolithic admission produce identical outputs for
    identical request sets even with fewer slots than requests."""
    params = _params()
    mono = _sched(params, None).generate(PROMPTS, max_new_tokens=[3, 8, 1, 5, 8])
    chun = _sched(params, 3).generate(PROMPTS, max_new_tokens=[3, 8, 1, 5, 8])
    assert mono == chun


def test_chunked_rejects_unsupported_configs():
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64,
                vocab=64, dtype=jnp.float32, remat=False)
    params_r = lm_init(jax.random.PRNGKey(0),
                       LMConfig(name="r", pattern=("rwkv",), **base))
    with pytest.raises(ValueError, match="attention-only"):
        Scheduler(LMConfig(name="r", pattern=("rwkv",), **base), params_r,
                  ServeConfig(), SchedulerConfig(prefill_chunk=4))
    cfg_m = LMConfig(name="m", ffn="moe", n_experts=4, top_k=2, **base)
    with pytest.raises(ValueError, match="attention-only"):
        Scheduler(cfg_m, lm_init(jax.random.PRNGKey(0), cfg_m),
                  ServeConfig(), SchedulerConfig(prefill_chunk=4))
    # xattn passes attn_only but has no encoder context when serving:
    # chunked admission must fail loudly, not emit silently wrong tokens
    cfg_x = LMConfig(name="x", pattern=("attn", "xattn"), n_image_tokens=4,
                     d_vision=8, **base)
    with pytest.raises(ValueError, match="xattn"):
        Scheduler(cfg_x, lm_init(jax.random.PRNGKey(0), cfg_x),
                  ServeConfig(), SchedulerConfig(prefill_chunk=4))
    cfg_a = LMConfig(name="a", **base)
    params_a = lm_init(jax.random.PRNGKey(0), cfg_a)
    with pytest.raises(ValueError, match="prefix_cache requires"):
        Scheduler(cfg_a, params_a, ServeConfig(),
                  SchedulerConfig(prefix_cache=True))
    # sliding-window ring smaller than cache_len: blocks not extractable
    cfg_l = LMConfig(name="l", pattern=("local", "attn"), window=8, **base)
    params_l = lm_init(jax.random.PRNGKey(0), cfg_l)
    with pytest.raises(ValueError, match="ring"):
        Scheduler(cfg_l, params_l, ServeConfig(),
                  SchedulerConfig(cache_len=64, prefill_chunk=4,
                                  prefix_cache=True))
    # ...but chunked prefill alone is fine on window layers
    sch = Scheduler(cfg_l, params_l, ServeConfig(max_new_tokens=6),
                    SchedulerConfig(cache_len=64, prefill_chunk=4))
    want = Engine(cfg_l, params_l, ServeConfig(max_new_tokens=6)
                  ).generate(PROMPTS[:3])
    assert sch.generate(PROMPTS[:3]) == want


def test_attn_chunk_apply_quantized_cache_branch():
    """The chunk-attention kernel also reads/writes quantized caches
    (dense and quantized twins must agree to quantization error, and the
    chunk's ring writes must equal kv_quantize of the dense writes)."""
    import numpy as np

    from repro.models.layers import (AttnSpec, attn_chunk_apply, attn_init,
                                     kv_quantize)

    spec = AttnSpec(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
    params = attn_init(jax.random.PRNGKey(0), spec)
    b, L, cw = 2, 16, 3
    # a dense cache holding positions 0..4, and its quantized twin
    pre = jax.random.normal(jax.random.PRNGKey(1), (b, L, 2, 8)) * 0.5
    pre = pre.at[:, 5:].set(0.0)
    dense_k, dense_v = pre, pre * 0.7
    q8 = {"k": kv_quantize(dense_k, 8), "v": kv_quantize(dense_v, 8)}
    x = jax.random.normal(jax.random.PRNGKey(2), (b, cw, 32),
                          dtype=jnp.float32)
    start = jnp.asarray([5, 5], jnp.int32)
    positions = start[:, None] + jnp.arange(cw)[None, :]
    lens = jnp.asarray([cw, 2], jnp.int32)       # one ragged row

    out_d, k_d, v_d = attn_chunk_apply(params, spec, x, positions, lens,
                                       dense_k, dense_v)
    out_q, k_q, v_q = attn_chunk_apply(params, spec, x, positions, lens,
                                       q8["k"], q8["v"])
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d),
                               atol=0.05)
    # ring writes: the quantized cache rows must be kv_quantize of the
    # dense rows the dense path wrote (pads dumped in both)
    for dn, qn in ((k_d, k_q), (v_d, v_q)):
        want = kv_quantize(dn, 8)
        np.testing.assert_array_equal(np.asarray(qn["codes"][:, 5:8]),
                                      np.asarray(want["codes"][:, 5:8]))
        # untouched slots keep their original quantized content
        np.testing.assert_array_equal(np.asarray(qn["codes"][:, :5]),
                                      np.asarray(q8["k"]["codes"][:, :5])
                                      if qn is k_q else
                                      np.asarray(q8["v"]["codes"][:, :5]))


def test_prefix_trie_bookkeeping():
    pc = PrefixCache(block=2, capacity_blocks=3)
    # a full-prompt match must leave >= 1 token to prefill
    pc.insert([1, 2, 3, 4], ["b0", "b1"])
    m, nodes = pc.lookup([1, 2, 3, 4])
    assert m == 2 and [n.payload for n in nodes] == ["b0"]
    pc.release(nodes)
    m, nodes = pc.lookup([1, 2, 3, 4, 9])
    assert m == 4 and [n.payload for n in nodes] == ["b0", "b1"]
    # pinned nodes survive capacity pressure; unpinned LRU leaves go first
    pc.insert([5, 6, 7, 8], ["c0", "c1"])       # 4 > 3 blocks: must evict
    assert pc.n_blocks == 3
    m2, again = pc.lookup([1, 2, 3, 4, 9])
    assert m2 == 4                              # pinned path intact
    pc.release(nodes)
    pc.release(again)
    with pytest.raises(RuntimeError):
        pc.release(again)
    # mismatched tokens never match
    m3, _ = pc.lookup([1, 9, 3, 4, 5])
    assert m3 == 0
    with pytest.raises(ValueError):
        pc.insert([1, 2], ["x", "y"])           # more blocks than prompt
