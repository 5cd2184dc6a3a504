"""The main-path Pallas kernels compile for a described TPU v5e at
granite-3-2b widths, with no chip attached (the TPU compiler is installed
with jaxlib): ``wqt_matmul`` / ``wqt_matmul_a8`` (the QTensor serving
matmuls), ``decode_attn`` / ``decode_attn_paged`` (quantized-KV decode)
and ``opt_step`` (the fused LOTION-AdamW step).  Each compiled program
must hold the kernel as a ``tpu_custom_call``.  The scheduler's whole
paged decode tick compiles too, and must leave the KV pool where it lies.

Interpret-mode tests cannot see what this sees: Mosaic refuses blocks
that break its tiling rules and shape casts it cannot lay out.  The
topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker
imports this file."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compiled_kernels
from repro.kernels.decode_attn.decode_attn import (decode_attn_paged_pallas,
                                                   decode_attn_pallas)
from repro.kernels.opt_step.opt_step import N_SCALARS, opt_step_pallas
from repro.kernels.wq_matmul.wq_matmul import (wqt_matmul_a8_pallas,
                                               wqt_matmul_pallas)

# granite-3-2b: d_model 2048, d_ff 8192, 32 heads / 8 KV heads, head_dim 64
D_MODEL, D_FF, N_KV, REP, HEAD_DIM = 2048, 8192, 8, 4, 64
SLOTS, CACHE_LEN, BLOCK, LAYERS = 8, 2304, 256, 40


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    """A described-device compile can be written to the persistent cache
    but never read back without a chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return compiled_kernels(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("a8", [False, True])
def test_wqt_matmul_compiles(one_chip, no_persistent_cache, m, bits, a8):
    """x (M, d_model) against an out-major (d_ff, d_model) int8 / packed
    int4 weight with one per-tensor scale — decode (M=8) and prefill."""
    kc = D_MODEL // 2 if bits == 4 else D_MODEL
    cdt = jnp.uint8 if bits == 4 else jnp.int8
    w = [((D_FF, kc), cdt), ((1, 1), jnp.float32)]
    if a8:
        got = _compiled(
            lambda x, xs, c, s: wqt_matmul_a8_pallas(
                x, xs, c, s, block_k=-1, int4=bits == 4, interpret=False),
            one_chip, ((m, D_MODEL), jnp.int8), ((m, 1), jnp.float32), *w)
        assert got["wqt_matmul_a8"] >= 1, got
    else:
        got = _compiled(
            lambda x, c, s: wqt_matmul_pallas(
                x, c, s, block_k=-1, int4=bits == 4, interpret=False),
            one_chip, ((m, D_MODEL), jnp.bfloat16), *w)
        assert got["wqt_matmul"] >= 1, got


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("paged", [False, True])
def test_decode_attn_compiles(one_chip, no_persistent_cache, bits, paged):
    """One decode step of 8 slots over a 2304-token int8 / int4 KV cache:
    the per-row ring, or 256-token blocks of one layer of the stacked
    paged pool."""
    hd_c = HEAD_DIM // 2 if bits == 4 else HEAD_DIM
    cdt = jnp.uint8 if bits == 4 else jnp.int8
    q = ((SLOTS, N_KV, REP, HEAD_DIM), jnp.bfloat16)
    pos = ((SLOTS,), jnp.int32)
    if paged:
        bps = CACHE_LEN // BLOCK
        nb = 1 + SLOTS * bps
        kv = [((LAYERS, nb, BLOCK, N_KV * hd_c), cdt),
              ((LAYERS, nb, N_KV, BLOCK), jnp.float32)]
        got = _compiled(
            lambda q, kc, ks, vc, vs, bt, p, r: decode_attn_paged_pallas(
                q, kc, ks, vc, vs, bt, p, r, bits=bits, interpret=False),
            one_chip, q, *kv, *kv, ((SLOTS, bps), jnp.int32), pos,
            ((), jnp.int32))
        assert got["decode_attn_paged"] == 1, got
    else:
        lead = (SLOTS, CACHE_LEN)
        kv = [((*lead, N_KV, hd_c), cdt), ((*lead, N_KV, 1), jnp.float32)]
        got = _compiled(
            lambda q, kc, ks, vc, vs, p: decode_attn_pallas(
                q, kc, ks, vc, vs, p, bits=bits, interpret=False),
            one_chip, q, *kv, *kv, pos)
        assert got["decode_attn"] == 1, got


@pytest.mark.parametrize("mode", ["scalar", "none"])
def test_opt_step_compiles(one_chip, no_persistent_cache, mode):
    """The fused LOTION-AdamW step over one (d_model, d_ff) fp32 weight in
    the wrapper's (R, 1024) view: with the per-tensor LOTION term, and as
    plain fused clip+AdamW."""
    rc = ((D_MODEL * D_FF // 1024, 1024), jnp.float32)
    got = _compiled(
        lambda w, g, m, n, sc: opt_step_pallas(
            w, g, m, n, sc, qmax=7.0, block_size=-1, fp4=False,
            penalty_mode=mode, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0,
            lam=1000.0, interpret=False),
        one_chip, rc, rc, rc, rc, ((1, N_SCALARS), jnp.float32))
    assert got["opt_step"] == 1, got


def _pool_moves(hlo: str, pool) -> list:
    """Instructions of a compiled program that copy, slice or write back
    a whole pool leaf or one layer of it (matched by element count, so a
    relayout of either is caught too)."""
    counts = {math.prod(a.shape[i:]) for a in jax.tree.leaves(pool)
              for i in (0, 1)}
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        name, dims, op = m.groups()
        moves = op in ("copy", "copy-start", "dynamic-slice",
                       "dynamic-update-slice") or (
            op == "fusion" and re.search(r"copy|dynamic", name))
        n = math.prod(int(d) for d in dims.split(",")) if dims else 1
        if moves and n in counts:
            out.append(line.strip()[:160])
    return out


def test_paged_tick_leaves_pool_in_place(one_chip, no_persistent_cache,
                                         monkeypatch):
    """The scheduler's paged decode tick at granite-3-2b size (40 layers,
    int4 weights and int4 KV, 32 slots x 2048 tokens in 256-token blocks,
    4 steps a tick, the pool donated as the scheduler donates it): each
    layer writes its token into the stacked pool and the kernel reads its
    blocks there, so no op copies, slices or writes back a layer's or the
    whole pool's bytes, and the program's temp stays under 1 GB (the
    per-layer slice-and-write-back carry held 9.08 GB)."""
    from repro.configs.granite_3_2b import config
    from repro.models.lm import init_paged_pool, lm_init
    from repro.serve.engine import ServeConfig, prepare_params
    from repro.serve.scheduler import decode_tick

    # compile the Mosaic kernels, as on the chip, not their interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = config()
    scfg = ServeConfig(weights="rtn:int4", kv_quant="int4", use_kernel=True)
    slots, bps = 32, 2048 // BLOCK
    i32 = jnp.int32
    params = jax.eval_shape(lambda: prepare_params(
        lm_init(jax.random.PRNGKey(0), cfg), scfg))
    pool = jax.eval_shape(lambda: init_paged_pool(
        cfg, slots * bps + 17, BLOCK, kv_quant="int4"))
    state = {k: jax.ShapeDtypeStruct((slots,), i32)
             for k in ("tok", "pos", "steps", "mnt", "eos")}
    state["active"] = jax.ShapeDtypeStruct((slots,), bool)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, pool, jax.ShapeDtypeStruct((slots, bps), i32), state, key))
    compiled = jax.jit(
        lambda p, pool, tables, state, key: decode_tick(
            cfg, scfg, 4, p, pool, state, key, tables),
        donate_argnums=(1, 3)).lower(*args).compile()
    hlo = compiled.as_text()
    assert compiled_kernels(hlo)["decode_attn_paged"] == 1
    assert _pool_moves(hlo, pool) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
