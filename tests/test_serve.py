"""Serving engine tests: batched generation, quantized-weight serving,
KV-quantized decode, wq-matmul integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import has_qtensor
from repro.models.lm import (LMConfig, lm_decode, lm_forward, lm_init,
                             lm_prefill)
from repro.serve import Engine, ServeConfig
from repro.serve.engine import bucket_cache_len

CFG = LMConfig(name="s", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=64, dtype=jnp.float32, remat=False)


def test_engine_greedy_deterministic():
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(weights="fp32", max_new_tokens=8))
    prompts = [[1, 2, 3], [4, 5], [6]]
    o1 = eng.generate(prompts)
    o2 = eng.generate(prompts)
    assert o1 == o2
    assert all(len(o) == 8 for o in o1)


@pytest.mark.parametrize("weights", ["rtn:int8", "rtn:int4", "rr:int4",
                                     "rtn:fp4"])
def test_engine_quantized_weights(weights):
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(weights=weights, max_new_tokens=4))
    outs = eng.generate([[1, 2, 3, 4]])
    assert len(outs[0]) == 4
    assert all(0 <= t < CFG.vocab for t in outs[0])


def test_int8_serving_close_to_fp32():
    """INT8-RTN serving matches fp32 generations on a trained-ish model
    most of the time (quantization-robust greedy argmax)."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    p_fp = Engine(CFG, params, ServeConfig(weights="fp32", max_new_tokens=12))
    p_q8 = Engine(CFG, params, ServeConfig(weights="rtn:int8",
                                           max_new_tokens=12))
    a = p_fp.generate([[1, 2, 3], [9, 8, 7]])
    b = p_q8.generate([[1, 2, 3], [9, 8, 7]])
    agree = np.mean([ai == bi for row_a, row_b in zip(a, b)
                     for ai, bi in zip(row_a, row_b)])
    assert agree > 0.5, agree


def test_kv_quantized_decode_close_to_fp():
    """int8 KV cache decode ~= bf16 cache decode (per-vector absmax)."""
    cfg = CFG
    params = lm_init(jax.random.PRNGKey(0), cfg)
    b, l = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, l), 0, cfg.vocab)
    full = lm_forward(params, cfg, toks)
    _, cache_q = lm_prefill(params, cfg, toks[:, :l - 1], cache_len=l,
                            kv_quant=True)
    ld, _ = lm_decode(params, cfg, cache_q, toks[:, l - 1:],
                      jnp.full((b,), l - 1, jnp.int32))
    err = np.abs(np.asarray(ld[:, 0] - full[:, l - 1]))
    rel = err.max() / max(np.abs(np.asarray(full[:, l - 1])).max(), 1e-6)
    assert rel < 0.08, rel   # int8 KV: small logit perturbation


def test_engine_quantized_storage_is_default_for_int():
    """rtn:int4 means STORED int4: the engine's prepared params hold
    QTensor codes, and generation still works end-to-end."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(weights="rtn:int4",
                                          max_new_tokens=4))
    assert has_qtensor(eng.params)
    outs = eng.generate([[1, 2, 3], [7]])
    assert all(len(o) == 4 for o in outs)
    # fp4 (codebook) falls back to the dense cast
    eng_fp4 = Engine(CFG, params, ServeConfig(weights="rtn:fp4",
                                              max_new_tokens=2))
    assert not has_qtensor(eng_fp4.params)
    # and an explicit opt-out restores the dense path for int too
    eng_dense = Engine(CFG, params, ServeConfig(weights="rtn:int4",
                                                quantized_storage=False,
                                                max_new_tokens=2))
    assert not has_qtensor(eng_dense.params)


def test_engine_quantized_storage_matches_dense_cast_serving():
    """Storage is a representation change only: QTensor serving and the
    legacy dense-dequantized serving produce THE SAME greedy tokens —
    per-tensor int8 dequantizes to identical floats on the jnp path, so
    any divergence here is a storage-path bug, not quantization noise."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    q = Engine(CFG, params, ServeConfig(weights="rtn:int8",
                                        max_new_tokens=16, use_kernel=False))
    d = Engine(CFG, params, ServeConfig(weights="rtn:int8",
                                        quantized_storage=False,
                                        max_new_tokens=16))
    prompts = [[1, 2, 3], [9, 8, 7, 6]]
    assert q.generate(prompts) == d.generate(prompts)


def test_bucket_cache_len_bounds_compiles():
    assert bucket_cache_len(1) == 16
    assert bucket_cache_len(16) == 16
    assert bucket_cache_len(17) == 32
    assert bucket_cache_len(100) == 128
    # distinct max_new_tokens within one bucket share one compiled decode
    buckets = {bucket_cache_len(8 + n) for n in range(1, 30)}
    assert len(buckets) <= 3, buckets


def test_engine_prompt_width_not_padded_beyond_batch_max():
    """Bucketing must not change generations: prompt width stays at the
    batch max (left-pad tokens are attended, so widening would shift
    every generation).  Identical prompts through engines built from the
    same params must generate identically regardless of other batch
    shapes served before."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    a = Engine(CFG, params, ServeConfig(weights="fp32", max_new_tokens=6))
    b = Engine(CFG, params, ServeConfig(weights="fp32", max_new_tokens=6))
    b.generate([[5] * 9])           # warm a different prompt width first
    assert a.generate([[1, 2, 3]]) == b.generate([[1, 2, 3]])


def test_engine_zero_new_tokens():
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(weights="fp32"))
    assert eng.generate([[1, 2], [3]], max_new_tokens=0) == [[], []]


def test_engine_generate_single_transfer_semantics():
    """Device-side token accumulation returns the same tokens as the
    seed-era per-token host loop (greedy, prefix property)."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(weights="fp32"))
    long = eng.generate([[1, 2, 3]], max_new_tokens=8)
    short = eng.generate([[1, 2, 3]], max_new_tokens=4)
    assert long[0][:4] == short[0]  # greedy decode is prefix-stable


@pytest.mark.parametrize("kv_quant", [True, "int4"])
@pytest.mark.parametrize("arch", ["gemma2-2b", "zamba2-2.7b"])
def test_kv_quant_cache_all_archs(arch, kv_quant):
    cfg = get_smoke_config(arch)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    b, l = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, l), 0, cfg.vocab)
    _, cache = lm_prefill(params, cfg, toks[:, :l - 1], cache_len=l,
                          kv_quant=kv_quant)
    ld, cache2 = lm_decode(params, cfg, cache, toks[:, l - 1:],
                           jnp.full((b,), l - 1, jnp.int32))
    assert np.isfinite(np.asarray(ld, np.float32)).all()
    # quantized entries preserved (int8 codes; packed uint8 for int4)
    want_dtype = jnp.uint8 if kv_quant == "int4" else jnp.int8
    leaves = jax.tree_util.tree_leaves_with_path(cache2)
    assert any(a.dtype == want_dtype for _, a in leaves)


@pytest.mark.parametrize("kv_quant,tol", [("int8", 0.08), ("int4", 0.45)])
def test_kv_quant_decode_close_to_dense(kv_quant, tol):
    """int8/int4 KV decode stays close to the dense-cache logits; int4
    (packed nibbles, 1/4 the cache bytes) is the coarser of the pair —
    ROADMAP PR 3 follow-up closing the weight/KV format gap."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    b, l = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, l), 0, CFG.vocab)
    pos = jnp.full((b,), l - 1, jnp.int32)
    _, cd = lm_prefill(params, CFG, toks[:, :l - 1], cache_len=l)
    ld_d, _ = lm_decode(params, CFG, cd, toks[:, l - 1:], pos)
    _, cq = lm_prefill(params, CFG, toks[:, :l - 1], cache_len=l,
                       kv_quant=kv_quant)
    ld_q, _ = lm_decode(params, CFG, cq, toks[:, l - 1:], pos)
    err = np.abs(np.asarray(ld_q - ld_d)).max()
    rel = err / max(np.abs(np.asarray(ld_d)).max(), 1e-6)
    assert rel < tol, (kv_quant, rel)


def test_int4_kv_pack_roundtrip():
    """Pack/unpack of int4 nibbles is exact on the full code range and
    ring decode writes preserve the packed layout."""
    from repro.models.layers import _pack_int4, _unpack_int4, kv_quantize

    codes = jnp.arange(-7, 8, dtype=jnp.int8).reshape(1, 1, 1, 15)
    codes = jnp.pad(codes, ((0, 0),) * 3 + ((0, 1),))     # even head_dim
    np.testing.assert_array_equal(np.asarray(_unpack_int4(_pack_int4(codes))),
                                  np.asarray(codes))
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 2, 8))
    q = kv_quantize(k, bits=4)
    assert q["codes"].dtype == jnp.uint8 and q["codes"].shape[-1] == 4
    deq = _unpack_int4(q["codes"]).astype(jnp.float32) * q["scale"]
    assert float(jnp.abs(deq - k).max()) <= float(q["scale"].max()) * 0.51


def test_engine_generate_through_int4_kv_cache():
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(max_new_tokens=6, kv_quant="int4"))
    outs = eng.generate([[1, 2, 3], [9, 8]])
    assert all(len(o) == 6 for o in outs)
    assert all(0 <= t < CFG.vocab for o in outs for t in o)


def test_engine_per_request_budgets_and_eos():
    """Per-request max_new_tokens / eos_id: each row is truncated to its
    own budget and stops at (and includes) its own EOS; greedy rows are
    prefix-stable so shorter budgets are prefixes of longer ones."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(max_new_tokens=8))
    base = eng.generate([[1, 2, 3], [9, 8, 7]])
    ragged = eng.generate([[1, 2, 3], [9, 8, 7]], max_new_tokens=[3, 7])
    assert ragged == [base[0][:3], base[1][:7]]
    # EOS = the first token whose FIRST occurrence in its row is at index
    # >= 2 (greedy rows may repeat one token, so base[r][2] can occur
    # earlier); that row stops there, the other row runs its full budget
    row, cut, eos = next((r, i, t) for r, out in enumerate(base)
                         for i, t in enumerate(out)
                         if i >= 2 and t not in out[:i])
    eos_ids = [eos if r == row else None for r in range(2)]
    stopped = eng.generate([[1, 2, 3], [9, 8, 7]], max_new_tokens=8,
                           eos_id=eos_ids)
    assert stopped[row] == base[row][:cut + 1] and stopped[row][-1] == eos
    assert stopped[1 - row] == base[1 - row]
    with pytest.raises(ValueError, match="entries"):
        eng.generate([[1]], max_new_tokens=[1, 2])


def test_engine_ragged_batch_is_pad_invariant():
    """Attention-only configs mask left pads (per-row prompt_lens): a
    prompt's generation no longer depends on its batchmates' lengths —
    the property that makes scheduler-vs-static parity possible at all."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    eng = Engine(CFG, params, ServeConfig(max_new_tokens=6))
    alone = [eng.generate([p])[0] for p in [[1, 2, 3], [4, 5], [6]]]
    batched = eng.generate([[1, 2, 3], [4, 5], [6]])
    assert batched == alone
