"""Fused quantized decode-attention Pallas TPU kernels.

One decode step against an int8 / packed-int4 KV cache, reading the code
bytes from HBM exactly once: nibble-unpack (int4), per-(slot, kv-head)
dequant, QK^T, ring-validity masking (+ sliding window, + logit softcap),
ONLINE softmax and PV accumulation all happen on the VMEM-resident tile —
the flash-attention dataflow of ``_streaming_sdpa`` collapsed into a
single kernel, so decode's HBM traffic per step per layer is the
*quantized* byte count (L*g*(hd/2 + 4) bytes for int4 instead of L*g*hd*2
for a bf16 cache).

Two entry points share one tile body (``_attend``): ``decode_attn_pallas``
streams a per-row ring cache, ``decode_attn_paged_pallas`` streams pool
blocks named by a block table (DESIGN.md §13).

Tile layout (what the TPU compiler accepts)
-------------------------------------------
A cache row's trailing ``(g, hd_c)`` dims are viewed flat, so one tile is
a lane-dense ``(tile, C)`` code block with ``C = g * hd_c`` and a
``(tile, g)`` scale block, both with full trailing dims — Mosaic refuses
a block of ONE kv head out of ``g``.  Every kv head is then scored by one
MXU contraction against a block-diagonal query: row ``(h, r)`` of
``qbd (g*rep, C)`` holds query ``q[h, r]`` in the columns of head ``h``
and zeros elsewhere, so ``qbd @ codes^T`` is exactly each query row
against its own head.  ``probs @ codes`` scores every head against every
row, and the wrapper keeps the diagonal blocks.  Packed int4 is never
interleaved in VMEM: the low and high nibble planes are the even and odd
head dims, contracted separately against the even and odd query columns
("parts"; 2 for int4, 1 for int8).

Numerics follow the jnp fallback in ``attn_decode``: codes contract raw,
the fp32 absmax scale folds into the (rows, tile) score / prob tiles
(expanded from the (tile, g) scale block by an exact one-hot matmul),
softcap applies before the validity bias, and a fully-masked tile's
garbage contribution is annihilated by the next valid tile's
``alpha = exp(-1e30 - m)`` rescale (decode always has >= 1 valid slot —
the just-written token).  Online vs. dense softmax differ only in fp
summation order.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

Array = jnp.ndarray
NEG_INF = -1e30

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _code_planes(codes, int4: bool):
    """(tile, C) code tile -> fp32 planes: [codes] for int8, or the
    sign-extended [low nibble, high nibble] planes (even / odd head dims)
    of packed int4."""
    x = codes.astype(jnp.int32)
    if not int4:
        return [x.astype(jnp.float32)]
    lo = ((x & 0xF) ^ 8) - 8
    hi = (((x >> 4) & 0xF) ^ 8) - 8
    return [lo.astype(jnp.float32), hi.astype(jnp.float32)]


def _dot_nt(a, b, precision=None):
    """a (m, k) . b (n, k)^T -> (m, n) fp32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _ring_valid(pos, start, tile: int, cache_len: int,
                window: Optional[int]):
    """(1, tile) validity of ring slots j = start + iota: slot j holds
    absolute position p_j = the largest p <= pos with p % cache_len == j."""
    j = start + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    p_j = pos - ((pos - j) % cache_len)
    valid = p_j >= 0
    if window is not None:
        valid &= (pos - p_j) < window
    return valid


def _attend(valid, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, m_ref, s_ref,
            acc_ref, *, rep: int, hd: int, softcap: Optional[float],
            int4: bool):
    """One tile of the online-softmax decode: q_ref (1, P, g*rep, C),
    code refs (1, tile, C), scale refs (1, tile, g); state in m/s
    (g*rep, 1) and acc (P, g*rep, C) scratch."""
    q = q_ref[0].astype(jnp.float32)                       # (P, GR, C)
    n_parts, gr = q.shape[0], q.shape[1]
    g = ks_ref.shape[-1]
    # one-hot (row -> kv head) expander: ksT[(h, r), l] = ks[l, h] exactly
    row_head = jax.lax.broadcasted_iota(jnp.int32, (gr, g), 0) // rep
    head = jax.lax.broadcasted_iota(jnp.int32, (gr, g), 1)
    expand = (row_head == head).astype(jnp.float32)
    hi_prec = jax.lax.Precision.HIGHEST

    k_planes = _code_planes(kc_ref[0], int4)
    s = _dot_nt(q[0], k_planes[0])
    for p in range(1, n_parts):
        s = s + _dot_nt(q[p], k_planes[p])                 # (GR, tile)
    ks_t = _dot_nt(expand, ks_ref[0], hi_prec)             # (GR, tile)
    logits = (s * ks_t) / np.sqrt(hd)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = logits + jnp.where(valid, 0.0, NEG_INF)

    m_prev = m_ref[...]                                    # (GR, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    prob = jnp.exp(logits - m_new)                         # (GR, tile)
    s_ref[...] = s_ref[...] * alpha + prob.sum(axis=-1, keepdims=True)

    pv = prob * _dot_nt(expand, vs_ref[0], hi_prec)
    v_planes = _code_planes(vc_ref[0], int4)
    for p in range(n_parts):
        acc_ref[p] = acc_ref[p] * alpha + jax.lax.dot_general(
            pv, v_planes[p], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_state(m_ref, s_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    s_ref[...] = jnp.zeros_like(s_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(o_ref, s_ref, acc_ref):
    denom = jnp.maximum(s_ref[...], 1e-30)                 # (GR, 1)
    for p in range(acc_ref.shape[0]):
        o_ref[0, p] = acc_ref[p] / denom


def _block_diag_query(q: Array, parts: int) -> Array:
    """(b, g, rep, hd) -> fp32 (b, parts, g*rep, g*hd/parts): part p holds
    head dims p::parts, row (h, r) nonzero only in head h's columns."""
    b, g, rep, hd = q.shape
    hp = hd // parts
    qp = jnp.moveaxis(q.astype(jnp.float32).reshape(b, g, rep, hp, parts),
                      -1, 1)                               # (b,P,g,rep,hp)
    eye = jnp.eye(g, dtype=bool)[None, None, :, None, :, None]
    qbd = jnp.where(eye, qp[:, :, :, :, None, :], 0.0)     # (b,P,g,rep,g,hp)
    return qbd.reshape(b, parts, g * rep, g * hp)


def _diag_out(o: Array, q: Array) -> Array:
    """Kernel output (b, parts, g*rep, C) -> (b, g, rep, hd) in q.dtype:
    keep each row's own-head block, re-interleave the parts."""
    b, g, rep, hd = q.shape
    parts = o.shape[1]
    hp = hd // parts
    o = jnp.diagonal(o.reshape(b, parts, g, rep, g, hp), axis1=2, axis2=4)
    o = jnp.moveaxis(o, -1, 2)                             # (b,P,g,rep,hp)
    o = jnp.moveaxis(o, 1, -1)                             # (b,g,rep,hp,P)
    return o.reshape(b, g, rep, hd).astype(q.dtype)


def _geometry(q, k_codes, k_scale, bits, lead):
    """Validate the cache leaf shapes and return (int4, parts, C)."""
    b, g, rep, hd = q.shape
    int4 = bits == 4
    hd_c = hd // 2 if int4 else hd
    if k_codes.shape != lead + (g, hd_c):
        raise ValueError(f"k_codes shape {k_codes.shape} != "
                         f"{lead + (g, hd_c)} for bits={bits}")
    if k_scale.shape != lead + (g, 1):
        raise ValueError(f"k_scale shape {k_scale.shape} != {lead + (g, 1)}")
    return int4, (2 if int4 else 1), g * hd_c


def _flat(codes, scale):
    """(..., g, hd_c) codes + (..., g, 1) scales -> (..., g*hd_c), (..., g)."""
    return (codes.reshape(codes.shape[:-2] + (-1,)),
            scale.reshape(scale.shape[:-1]))


# --------------------------------------------------------------------------
# Ring cache: codes (b, L, g, hd[/2]), one cache per batch row
# --------------------------------------------------------------------------

def _decode_attn_kernel(pos_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                        o_ref, m_ref, s_ref, acc_ref, *,
                        n_l: int, tile_l: int, cache_len: int,
                        window: Optional[int], softcap: Optional[float],
                        int4: bool, rep: int, hd: int):
    bi = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _():
        _init_state(m_ref, s_ref, acc_ref)

    valid = _ring_valid(pos_ref[bi], li * tile_l, tile_l, cache_len, window)
    _attend(valid, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, m_ref, s_ref,
            acc_ref, rep=rep, hd=hd, softcap=softcap, int4=int4)

    @pl.when(li == n_l - 1)
    def _():
        _finalize(o_ref, s_ref, acc_ref)


def _pick_tile_l(cache_len: int, pref: int) -> int:
    for cand in (pref, 512, 256, 128, 64, 32, 16, 8):
        if cand <= cache_len and cache_len % cand == 0:
            return cand
    return cache_len


def _scratch(parts: int, gr: int, c: int):
    return [pltpu.VMEM((gr, 1), jnp.float32),
            pltpu.VMEM((gr, 1), jnp.float32),
            pltpu.VMEM((parts, gr, c), jnp.float32)]


def decode_attn_pallas(q: Array, k_codes: Array, k_scale: Array,
                       v_codes: Array, v_scale: Array, pos: Array, *,
                       bits: int = 8, window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       block_l: int = 256,
                       interpret: Optional[bool] = None) -> Array:
    """q (b, g, rep, hd) x quantized ring cache -> (b, g, rep, hd).

    ``k_codes``/``v_codes``: int8 (b, L, g, hd) or packed-int4 uint8
    (b, L, g, hd/2); scales (b, L, g, 1) fp32; ``pos`` (b,) int32.
    """
    b, g, rep, hd = q.shape
    L = k_codes.shape[1]
    int4, parts, c = _geometry(q, k_codes, k_scale, bits, (b, L))
    tile_l = _pick_tile_l(L, block_l)
    n_l = L // tile_l
    gr = g * rep
    kc, ks = _flat(k_codes, k_scale)
    vc, vs = _flat(v_codes, v_scale)

    kernel = functools.partial(
        _decode_attn_kernel, n_l=n_l, tile_l=tile_l, cache_len=L,
        window=window, softcap=softcap, int4=int4, rep=rep, hd=hd)
    # index maps see the scalar-prefetch ref as a trailing arg
    q_spec = pl.BlockSpec((1, parts, gr, c), lambda bi, li, ps: (bi, 0, 0, 0))
    code_spec = pl.BlockSpec((1, tile_l, c), lambda bi, li, ps: (bi, li, 0))
    scale_spec = pl.BlockSpec((1, tile_l, g), lambda bi, li, ps: (bi, li, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, n_l),
        in_specs=[q_spec, code_spec, scale_spec, code_spec, scale_spec],
        out_specs=q_spec, scratch_shapes=_scratch(parts, gr, c))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, parts, gr, c), jnp.float32),
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret), name="decode_attn",
    )(pos.astype(jnp.int32), _block_diag_query(q, parts), kc, ks, vc, vs)
    return _diag_out(out, q)


# --------------------------------------------------------------------------
# Paged variant (DESIGN.md §13): the KV lives in a SHARED block pool
# ((n_blocks, bs, g, hd[/2]) codes + (n_blocks, bs, g, 1) scales) and each
# batch row's cache is named by an int32 block table (b, bps).  The grid is
# (batch, bps) with the BLOCK axis innermost/"arbitrary": the flattened
# table and the positions ride scalar prefetch, so tile li of row bi
# streams pool block ``bt[bi, li]`` from HBM — one tile per logical block,
# same online-softmax dataflow and validity math as the ring kernel with
# tile_l = block_size and slots j = li*bs + iota.
# --------------------------------------------------------------------------

def _decode_attn_paged_kernel(bt_ref, pos_ref, q_ref, kc_ref, ks_ref,
                              vc_ref, vs_ref, o_ref, m_ref, s_ref, acc_ref,
                              *, bps: int, block_size: int,
                              window: Optional[int],
                              softcap: Optional[float], int4: bool,
                              rep: int, hd: int):
    del bt_ref                       # consumed by the index maps
    bi = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _():
        _init_state(m_ref, s_ref, acc_ref)

    # ring validity over LOGICAL slots j = li*bs + iota — table entry li
    # of a row holds exactly ring slots [li*bs, (li+1)*bs), so the dense
    # formula carries over unchanged
    valid = _ring_valid(pos_ref[bi], li * block_size, block_size,
                        bps * block_size, window)
    _attend(valid, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, m_ref, s_ref,
            acc_ref, rep=rep, hd=hd, softcap=softcap, int4=int4)

    @pl.when(li == bps - 1)
    def _():
        _finalize(o_ref, s_ref, acc_ref)


def decode_attn_paged_pallas(q: Array, k_codes: Array, k_scale: Array,
                             v_codes: Array, v_scale: Array,
                             block_tables: Array, pos: Array, *,
                             bits: int = 8, window: Optional[int] = None,
                             softcap: Optional[float] = None,
                             interpret: Optional[bool] = None) -> Array:
    """q (b, g, rep, hd) x paged quantized pool -> (b, g, rep, hd).

    ``k_codes``/``v_codes``: int8 (n_blocks, bs, g, hd) or packed-int4
    uint8 (n_blocks, bs, g, hd/2); scales (n_blocks, bs, g, 1) fp32;
    ``block_tables`` (b, bps) int32 pool block ids; ``pos`` (b,) int32.
    """
    b, g, rep, hd = q.shape
    n_blocks, bs = k_codes.shape[0], k_codes.shape[1]
    int4, parts, c = _geometry(q, k_codes, k_scale, bits, (n_blocks, bs))
    bps = block_tables.shape[1]
    gr = g * rep
    kc, ks = _flat(k_codes, k_scale)
    vc, vs = _flat(v_codes, v_scale)

    kernel = functools.partial(
        _decode_attn_paged_kernel, bps=bps, block_size=bs,
        window=window, softcap=softcap, int4=int4, rep=rep, hd=hd)
    # index maps see the scalar-prefetch refs as trailing args: tile li of
    # row bi reads pool block bt[bi * bps + li]
    q_spec = pl.BlockSpec((1, parts, gr, c),
                          lambda bi, li, bt, ps: (bi, 0, 0, 0))
    code_spec = pl.BlockSpec((1, bs, c),
                             lambda bi, li, bt, ps: (bt[bi * bps + li], 0, 0))
    scale_spec = pl.BlockSpec((1, bs, g),
                              lambda bi, li, bt, ps: (bt[bi * bps + li], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, bps),
        in_specs=[q_spec, code_spec, scale_spec, code_spec, scale_spec],
        out_specs=q_spec, scratch_shapes=_scratch(parts, gr, c))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, parts, gr, c), jnp.float32),
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret), name="decode_attn_paged",
    )(block_tables.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32),
      _block_diag_query(q, parts), kc, ks, vc, vs)
    return _diag_out(out, q)
