"""QTensor quantized-storage serving tests: container round-trip vs the
``quantize_store``/``dequantize_store`` reference, the transposed-layout
wq_matmul kernel, pytree/jit/scan survival, serving parity end-to-end
through prefill+decode, sharding congruence, checkpointing, and the
no-dense-materialization guarantee of the kernel path."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import (QTensor, QuantPolicy, dequantize_params, get_format,
                        qtensor_use_kernel, quantize_params, quantize_qtensor)
from repro.core.quantize import dequantize_store, quantize_store
from repro.distributed.sharding import _leaf_name, param_spec, params_shardings
from repro.kernels.wq_matmul import wqt_matmul
from repro.kernels.wq_matmul.ref import wqt_matmul_ref
from repro.launch.mesh import make_mesh
from repro.models.lm import LMConfig, lm_decode, lm_init, lm_prefill

POLICY = QuantPolicy(min_size=256, include_embeddings=True)

CFG_TIED = LMConfig(name="qt-tied", n_layers=2, d_model=128, n_heads=4,
                    n_kv_heads=2, d_ff=256, vocab=256, dtype=jnp.float32,
                    remat=False)
CFG_UNTIED = LMConfig(name="qt-untied", n_layers=2, d_model=128, n_heads=4,
                      n_kv_heads=2, d_ff=256, vocab=256, dtype=jnp.float32,
                      remat=False, tie_embeddings=False)
CFG_MOE = LMConfig(name="qt-moe", n_layers=2, d_model=128, n_heads=4,
                   n_kv_heads=2, d_ff=128, vocab=256, dtype=jnp.float32,
                   remat=False, ffn="moe", n_experts=4, top_k=2)


def _rand(shape, seed=0, scale=0.5):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


# --------------------------------------------------------------------------
# container <-> quantize_store parity (the layout contract)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["int8", "int4"])
@pytest.mark.parametrize("block_k", [-1, 128])
@pytest.mark.parametrize("shape", [(96, 256), (3, 64, 128)])
def test_qtensor_dequant_matches_dequantize_store(fmt, block_k, shape):
    """A QTensor is quantize_store output in the out-major layout: its
    dequantization must reproduce dequantize_store's values exactly."""
    stored = _rand(shape, seed=1)
    f = get_format(fmt)
    qt = quantize_qtensor(stored, f, block_k)
    codes, scales, meta = quantize_store(
        stored.astype(jnp.float32), f, block_k)
    want = dequantize_store(codes, scales, meta, f)
    np.testing.assert_allclose(np.asarray(qt.dequantize()),
                               np.asarray(want), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("block_k", [-1, 128])
def test_qtensor_rr_storage_is_exact(block_k):
    """mode='rr' stores the randomized-rounding cast bit-exactly: the RR
    cast runs in the stored orientation, so re-quantizing a QTensor's own
    dequantization reproduces identical codes and scales (no silent
    second rounding — the grid and blocks coincide)."""
    params = {"wq": _rand((128, 256), seed=8)}
    qp = quantize_params(params, "int4", QuantPolicy(min_size=256),
                         block_k, mode="rr", key=jax.random.PRNGKey(3))
    qt = qp["wq"]
    assert isinstance(qt, QTensor)
    again = quantize_qtensor(qt.dequantize(), get_format("int4"), block_k)
    np.testing.assert_array_equal(np.asarray(qt.codes),
                                  np.asarray(again.codes))
    np.testing.assert_array_equal(np.asarray(qt.scales),
                                  np.asarray(again.scales))


def test_qtensor_int4_packing_halves_codes():
    qt8 = quantize_qtensor(_rand((64, 128)), get_format("int8"), -1)
    qt4 = quantize_qtensor(_rand((64, 128)), get_format("int4"), -1)
    assert qt8.codes.shape == (64, 128) and qt8.codes.dtype == jnp.int8
    assert qt4.codes.shape == (64, 64) and qt4.codes.dtype == jnp.uint8
    assert qt4.in_dim == 128 and qt4.shape == (64, 128)


def test_qtensor_rejects_bad_layouts():
    with pytest.raises(ValueError):
        quantize_qtensor(_rand((64, 130)), get_format("int8"), 128)
    with pytest.raises(ValueError):
        quantize_qtensor(_rand((64, 65)), get_format("int4"), -1)
    with pytest.raises(ValueError):
        quantize_qtensor(_rand((64, 128)), get_format("fp4"), -1)


# --------------------------------------------------------------------------
# transposed-layout kernel vs oracle (incl. ragged decode M)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("block_k", [-1, 128])
@pytest.mark.parametrize("m", [1, 12, 130])
def test_wqt_matmul_matches_ref(bits, block_k, m):
    n, k = 200, 256
    w = _rand((k, n), seed=2)
    x = _rand((m, k), seed=3).astype(jnp.float32)
    from repro.core.qtensor import from_matmul_weight
    qt = from_matmul_weight(w, get_format(f"int{bits}"), block_k)
    got = wqt_matmul(x, qt.codes, qt.scales, block_k=block_k, bits=bits)
    want = wqt_matmul_ref(x, qt.codes, qt.scales, block_k, bits == 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_qtensor_matmul_batched_moe_layout():
    """3-D (expert-stacked) QTensor matmul: kernel (lax.map) and jnp
    fallback agree with the dense einsum."""
    from repro.core.qtensor import from_matmul_weight, matmul
    e, m, k, n = 3, 6, 64, 96
    w = _rand((e, k, n), seed=4)
    x = _rand((e, m, k), seed=5)
    qt = from_matmul_weight(w, get_format("int8"), -1)
    want = jnp.einsum("emk,ekn->emn", x,
                      jnp.swapaxes(qt.dequantize(), -1, -2))
    for flag in (True, False):
        with qtensor_use_kernel(flag):
            got = matmul(x, qt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# pytree behavior: jit, scan slicing, tree ops keep meta
# --------------------------------------------------------------------------

def test_qtensor_survives_jit_and_scan():
    qt = quantize_qtensor(_rand((4, 64, 128), seed=6), get_format("int4"), -1)
    x = _rand((4, 8, 128), seed=7)

    from repro.core.qtensor import matmul

    @jax.jit
    def scanned(x, qt):
        def body(carry, sl):
            qt_i, x_i = sl
            return carry + matmul(x_i, qt_i).sum(), None
        out, _ = jax.lax.scan(body, jnp.zeros(()), (qt, x))
        return out

    got = scanned(x, qt)
    want = sum(matmul(x[i], jax.tree.map(lambda a: a[i], qt)).sum()
               for i in range(4))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# --------------------------------------------------------------------------
# serving parity: quantized storage == dense-dequantized serving
# --------------------------------------------------------------------------

def _parity(cfg, fmt, block_k, use_kernel, tol=2e-3):
    params = lm_init(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, fmt, POLICY, block_k)
    dp = dequantize_params(qp)
    b, l = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, l), 0, cfg.vocab)
    pos = jnp.full((b,), l - 1, jnp.int32)
    with qtensor_use_kernel(use_kernel):
        lg_q, cache = jax.jit(lambda p, t: lm_prefill(
            p, cfg, t, cache_len=l + 2))(qp, toks)
        ld_q, _ = jax.jit(lambda p, c, t, po: lm_decode(
            p, cfg, c, t, po))(qp, cache, toks[:, -1:], pos)
    lg_d, cache_d = jax.jit(lambda p, t: lm_prefill(
        p, cfg, t, cache_len=l + 2))(dp, toks)
    ld_d, _ = jax.jit(lambda p, c, t, po: lm_decode(
        p, cfg, c, t, po))(dp, cache_d, toks[:, -1:], pos)
    np.testing.assert_allclose(np.asarray(lg_q), np.asarray(lg_d), atol=tol)
    np.testing.assert_allclose(np.asarray(ld_q), np.asarray(ld_d), atol=tol)


@pytest.mark.parametrize("fmt", ["int8", "int4"])
@pytest.mark.parametrize("block_k", [-1, 128])
def test_serving_parity_tied(fmt, block_k):
    """Tied-embedding prefill+decode with QTensor storage matches the
    dense dequantize_store reference (jnp dispatch)."""
    _parity(CFG_TIED, fmt, block_k, use_kernel=False, tol=1e-5)


def test_serving_parity_untied_kernel():
    _parity(CFG_UNTIED, "int4", 128, use_kernel=True)


def test_serving_parity_tied_kernel():
    _parity(CFG_TIED, "int8", -1, use_kernel=True)


def test_serving_parity_moe():
    _parity(CFG_MOE, "int4", -1, use_kernel=False, tol=1e-5)


# --------------------------------------------------------------------------
# no dense weight materialization in the jitted decode (kernel path)
# --------------------------------------------------------------------------

def test_decode_jaxpr_has_no_dense_weight_materialization():
    import benchmarks.bench_serve as bs
    cfg = CFG_TIED
    params = lm_init(jax.random.PRNGKey(0), cfg)
    shapes = bs.dense_weight_shapes(params)
    qp = quantize_params(params, "int4", POLICY, -1)
    b = 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, 8), 0, cfg.vocab)
    with qtensor_use_kernel(True):
        _, cache = jax.jit(lambda p, t: lm_prefill(
            p, cfg, t, cache_len=12))(qp, toks)
        bad = bs.jaxpr_dense_materializations(
            lambda p, c, t, po: lm_decode(p, cfg, c, t, po),
            (qp, cache, toks[:, -1:], jnp.full((b,), 7, jnp.int32)), shapes)
    assert not bad, bad
    # the jnp fallback legitimately dequantizes (that is its contract) —
    # the checker must SEE it, or the assert above is vacuous
    with qtensor_use_kernel(False):
        bad_ref = bs.jaxpr_dense_materializations(
            lambda p, c, t, po: lm_decode(p, cfg, c, t, po),
            (qp, cache, toks[:, -1:], jnp.full((b,), 7, jnp.int32)), shapes)
    assert bad_ref, "checker failed to flag the dequantizing fallback"


# --------------------------------------------------------------------------
# sharding: codes and scales congruent, derived from the weight's rule
# --------------------------------------------------------------------------

def test_qtensor_sharding_specs_congruent():
    params = lm_init(jax.random.PRNGKey(0), CFG_TIED)
    qp = quantize_params(params, "int4", POLICY, 128)
    flat, _ = jax.tree_util.tree_flatten_with_path(qp)
    by_parent = {}
    for p, x in flat:
        name = _leaf_name(p)
        if name.endswith(("/codes", "/scales")):
            parent, field = name.rsplit("/", 1)
            by_parent.setdefault(parent, {})[field] = param_spec(p, x)
    assert by_parent, "no QTensor leaves found"
    for parent, specs in by_parent.items():
        assert specs["codes"] == specs["scales"], (parent, specs)
    # out-major storage: the model axis of a col-parallel weight (dense
    # (d, out) -> P(data, model)) lands on the stored FIRST trailing dim
    wq = [s for n, s in ((p, s["codes"]) for p, s in by_parent.items())
          if n.endswith("/wq")]
    assert wq and tuple(wq[0])[-2:] == ("model", "data"), wq
    # placement smoke on a real mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = params_shardings(mesh, jax.eval_shape(lambda: qp))
    jax.device_put(qp, sh)


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def test_qtensor_checkpoint_roundtrip():
    params = lm_init(jax.random.PRNGKey(0), CFG_TIED)
    qp = quantize_params(params, "int4", POLICY, 128)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"params": qp})
        out, step = ckpt.load(d, {"params": qp})
    assert step == 1
    for a, b in zip(jax.tree.leaves({"params": qp}), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    qts = [t for t in jax.tree_util.tree_leaves(
        out, is_leaf=lambda t: isinstance(t, QTensor))
        if isinstance(t, QTensor)]
    assert qts and all(t.bits == 4 and t.block_k == 128 for t in qts)
