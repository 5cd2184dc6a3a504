"""Weight-only-quantized matmul kernels (pl.pallas_call + BlockSpec).

Two entry points over the same dequant-in-VMEM dataflow:

``wq_matmul_pallas`` — the original K-major layout:

  x      (M, K)      bf16/f32 activations
  codes  (K, N)      int8   — or packed int4: (K//2, N) uint8, two K-values
                     per byte (even K in low nibble)
  scales (K//bs, N)  f32    — one scale per (K-block, column), i.e. the
                     blockwise absmax layout with blocks along K, so a
                     whole (TK=bs, TN) tile shares one scale row

``wqt_matmul_pallas`` — the transposed QTensor storage layout (out-major,
contraction along the stored LAST axis; see DESIGN.md §6), computing
``x @ dequant(stored)^T``:

  x      (M, K)
  codes  (N, K)      int8   — or packed int4: (N, K//2) uint8, two
                     K-values per byte (even K in low nibble)
  scales (N, K//bs)  f32 blockwise, or (1, 1) per-tensor (one scalar per
                     matrix, the paper's LLM setting)

This is the serving path for every QTensor weight, including the
tied-embedding head where the (vocab, d) table already sits in the
out-major layout.

Grid (M/TM, N/TN, K/TK) with a VMEM fp32 accumulator scratch; the dequant
(convert + scale multiply) happens on the weight tile already resident
in VMEM, feeding the MXU dot — the HBM read is 1 byte (or half) per
weight instead of 2-4, which is the whole point of serving INT4/INT8
models (decode is weight-bandwidth-bound).  K tiles are the innermost
("arbitrary") grid dim; output is written on the last K step.

Edge handling: M (decode batch — 1, 8, 12, ... rather than a multiple of
128) and N are padded *inside* the pallas wrappers to the tile grid and
sliced back; K tiles stay locked to the quant block so the scale
BlockSpec indexing is exact.  TPU alignment: TN multiple of 128 (lanes)
for large N, TK = bs multiple of 8.

Packed int4 never interleaves nibbles in VMEM (Mosaic refuses that
shape cast).  The wrapper splits the activations into their even and odd
K columns instead, and the kernel contracts each half against one nibble
plane: ``x @ w^T = x[:, 0::2] @ lo^T + x[:, 1::2] @ hi^T``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pick_tile_k(K: int, pref: int = 512) -> int:
    """Largest 8-aligned divisor of K up to ``pref`` (whole K if none):
    the per-tensor path has no quant block locking the K tile, so pick
    something VMEM-friendly that still divides K exactly."""
    for cand in (pref, 384, 256, 128, 64, 32, 16, 8):
        if cand <= K and K % cand == 0:
            return cand
    return K


def _nibble_planes(codes):
    """Packed uint8 int4 codes -> (lo, hi) int32 planes of the same shape
    holding the sign-extended even-K (low nibble) and odd-K (high nibble)
    values."""
    x = codes.astype(jnp.int32)
    lo = ((x & 0xF) ^ 8) - 8
    hi = (((x >> 4) & 0xF) ^ 8) - 8
    return lo, hi


def _split_k(x):
    """(M, K) activations -> their even and odd K columns, the halves that
    contract against the low and high nibble planes."""
    return x[:, 0::2], x[:, 1::2]


def _wq_kernel(*refs, n_k, int4):
    if int4:
        xe_ref, xo_ref, c_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, c_ref, s_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[...]                       # (1, TN) fp32

    def dot(x, w):                       # x (TM, tk) . w (tk, TN)
        wd = w.astype(jnp.float32) * s   # dequant in VMEM
        return jax.lax.dot_general(
            x.astype(jnp.float32), wd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if int4:                             # codes (TK//2, TN): K rows paired
        lo, hi = _nibble_planes(c_ref[...])
        acc_ref[...] += dot(xe_ref[...], lo) + dot(xo_ref[...], hi)
    else:
        acc_ref[...] += dot(x_ref[...], c_ref[...])

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def wq_matmul_pallas(x, codes, scales, *, block_k: int, int4: bool,
                     tile_m: int = 128, tile_n: int = 128,
                     interpret: Optional[bool] = None):
    """x (M, K) @ dequant(codes, scales) -> (M, N)."""
    M, K = x.shape
    N = codes.shape[1]
    # the K tile is LOCKED to the quant block: the scale BlockSpec below
    # indexes scale rows by the K-*tile* grid index, which covers the right
    # (block, column) scale row only when one K tile == one quant block.
    tile_k = block_k
    # M edge: decode batches are small and ragged (1, 8, 12, ...) — pad x
    # up to an 8-aligned tile grid here and slice the output back, so
    # callers never need M % tile_m == 0
    tile_m = min(tile_m, _round_up(M, 8))
    m_pad = _round_up(M, tile_m)
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    tile_n = min(tile_n, N)
    assert N % tile_n == 0 and K % tile_k == 0
    assert scales.shape == (K // block_k, N), scales.shape
    n_k = K // tile_k
    grid = (m_pad // tile_m, N // tile_n, n_k)

    if int4:
        assert tile_k % 2 == 0 and codes.shape == (K // 2, N)
        xs = _split_k(x)
        tk = tile_k // 2
    else:
        assert codes.shape == (K, N)
        xs = (x,)
        tk = tile_k
    x_spec = pl.BlockSpec((tile_m, tk), lambda i, j, k: (i, k))
    c_spec = pl.BlockSpec((tk, tile_n), lambda i, j, k: (k, j))
    s_spec = pl.BlockSpec((1, tile_n), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j))

    out = pl.pallas_call(
        functools.partial(_wq_kernel, n_k=n_k, int4=int4),
        grid=grid,
        in_specs=[x_spec] * len(xs) + [c_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret), name="wq_matmul",
    )(*xs, codes, scales)
    return out[:M] if m_pad != M else out


# --------------------------------------------------------------------------
# Transposed (out-major) layout: the QTensor serving entry point
# --------------------------------------------------------------------------

def _wqt_kernel(*refs, n_k, int4, per_tensor):
    if int4:
        xe_ref, xo_ref, c_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, c_ref, s_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[...]                       # (TN, 1) blockwise | (1, 1) scalar
    scale = s[0, 0] if per_tensor else s

    def dot(x, w):                       # x (TM, tk) . w (TN, tk)^T
        wd = w.astype(jnp.float32) * scale
        return jax.lax.dot_general(
            x.astype(jnp.float32), wd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    if int4:                             # codes (TN, TK//2)
        lo, hi = _nibble_planes(c_ref[...])
        acc_ref[...] += dot(xe_ref[...], lo) + dot(xo_ref[...], hi)
    else:
        acc_ref[...] += dot(x_ref[...], c_ref[...])

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _wqt_a8_kernel(*refs, n_k, int4, per_tensor):
    """W4A8/W8A8 epilogue variant of ``_wqt_kernel``: activations arrive
    as per-row int8 codes + fp32 row scales, the contraction runs
    int8 x int8 -> int32 on the MXU, and BOTH scales fold into the fp32
    accumulate — no dequantized operand is ever materialized.  Exact per
    K-tile because the row scale does not depend on K."""
    if int4:
        xe_ref, xo_ref, xs_ref, c_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, xs_ref, c_ref, s_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def dot(xq, w):                      # int8 (TM, tk) . int8 (TN, tk)^T
        return jax.lax.dot_general(
            xq, w.astype(jnp.int8), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)

    if int4:
        lo, hi = _nibble_planes(c_ref[...])
        prod = dot(xe_ref[...], lo) + dot(xo_ref[...], hi)
    else:
        prod = dot(x_ref[...], c_ref[...])           # (TM, TN) int32
    xs = xs_ref[...]                     # (TM, 1) fp32 row scales
    s = s_ref[...]                       # (TN, 1) blockwise | (1, 1) scalar
    ws = s[0, 0] if per_tensor else s[:, 0][None, :]
    acc_ref[...] += prod.astype(jnp.float32) * (xs * ws)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _wqt_geometry(x, codes, scales, block_k, int4, tile_m, tile_n):
    """Shared tiling for the out-major kernels: K tile, padded M/N, and
    the grid.  Returns (x, codes, scales, tile_k, tile_m, tile_n, grid)
    with x/codes/scales padded to the tile grid."""
    M, K = x.shape
    N = codes.shape[0]
    per_tensor = block_k == -1
    if per_tensor:
        assert scales.shape[-2:] == (1, 1), scales.shape
        tile_k = _pick_tile_k(K)
    else:
        tile_k = block_k
        assert K % tile_k == 0, (K, tile_k)
        assert scales.shape == (N, K // block_k), scales.shape
    if int4:
        assert tile_k % 2 == 0 and codes.shape == (N, K // 2), codes.shape
    else:
        assert codes.shape == (N, K), codes.shape
    tile_m = min(tile_m, _round_up(M, 8))
    m_pad = _round_up(M, tile_m)
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    tile_n = min(tile_n, _round_up(N, 8))
    n_pad = _round_up(N, tile_n)
    if n_pad != N:
        codes = jnp.pad(codes, ((0, n_pad - N), (0, 0)))
        if not per_tensor:
            scales = jnp.pad(scales, ((0, n_pad - N), (0, 0)))
    grid = (m_pad // tile_m, n_pad // tile_n, K // tile_k)
    return x, codes, scales, tile_k, tile_m, tile_n, grid


def _wqt_specs(int4, per_tensor, tile_k, tile_m, tile_n):
    """(activation, codes, scales, out) BlockSpecs of the out-major
    kernels; int4 activations arrive as two half-K operands."""
    tk = tile_k // 2 if int4 else tile_k
    x_spec = pl.BlockSpec((tile_m, tk), lambda i, j, k: (i, k))
    c_spec = pl.BlockSpec((tile_n, tk), lambda i, j, k: (j, k))
    if per_tensor:
        s_spec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    else:
        s_spec = pl.BlockSpec((tile_n, 1), lambda i, j, k: (j, k))
    o_spec = pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j))
    return x_spec, c_spec, s_spec, o_spec


def wqt_matmul_a8_pallas(xq, xs, codes, scales, *, block_k: int, int4: bool,
                         tile_m: int = 128, tile_n: int = 128,
                         interpret: Optional[bool] = None):
    """int8 xq (M, K) + row scales xs (M, 1) against out-major quantized
    weights -> fp32 (M, N).  Same tiling/edge-padding rules as
    ``wqt_matmul_pallas`` (padded activation rows get zero codes and
    scale 1, and are sliced back off)."""
    M, N = xq.shape[0], codes.shape[0]
    assert xs.shape == (M, 1), xs.shape
    per_tensor = block_k == -1
    xq, codes, scales, tile_k, tile_m, tile_n, grid = _wqt_geometry(
        xq, codes, scales, block_k, int4, tile_m, tile_n)
    if xq.shape[0] != M:
        xs = jnp.pad(xs, ((0, xq.shape[0] - M), (0, 0)),
                     constant_values=1.0)
    x_spec, c_spec, s_spec, o_spec = _wqt_specs(int4, per_tensor, tile_k,
                                                tile_m, tile_n)
    xs_spec = pl.BlockSpec((tile_m, 1), lambda i, j, k: (i, 0))
    x_ops = _split_k(xq) if int4 else (xq,)

    out = pl.pallas_call(
        functools.partial(_wqt_a8_kernel, n_k=grid[2], int4=int4,
                          per_tensor=per_tensor),
        grid=grid,
        in_specs=[x_spec] * len(x_ops) + [xs_spec, c_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((grid[0] * tile_m, grid[1] * tile_n),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret), name="wqt_matmul_a8",
    )(*x_ops, xs, codes, scales)
    if out.shape != (M, N):
        out = out[:M, :N]
    return out


def wqt_matmul_pallas(x, codes, scales, *, block_k: int, int4: bool,
                      tile_m: int = 128, tile_n: int = 128,
                      interpret: Optional[bool] = None):
    """x (M, K) @ dequant(codes (N, K[/2]), scales)^T -> (M, N).

    ``block_k == -1`` is the per-tensor mode: ``scales`` is a (1, 1)
    scalar shared by the whole matrix and the K tile is free; otherwise
    the K tile is locked to the quant block (``scales`` is (N, K//bs)).
    M and N edges are padded to the tile grid and sliced back.
    """
    M, N = x.shape[0], codes.shape[0]
    per_tensor = block_k == -1
    x, codes, scales, tile_k, tile_m, tile_n, grid = _wqt_geometry(
        x, codes, scales, block_k, int4, tile_m, tile_n)
    x_spec, c_spec, s_spec, o_spec = _wqt_specs(int4, per_tensor, tile_k,
                                                tile_m, tile_n)
    x_ops = _split_k(x) if int4 else (x,)

    out = pl.pallas_call(
        functools.partial(_wqt_kernel, n_k=grid[2], int4=int4,
                          per_tensor=per_tensor),
        grid=grid,
        in_specs=[x_spec] * len(x_ops) + [c_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((grid[0] * tile_m, grid[1] * tile_n),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=resolve_interpret(interpret), name="wqt_matmul",
    )(*x_ops, codes, scales)
    if out.shape != (M, N):
        out = out[:M, :N]
    return out
