"""Public wrapper for the weight-quantized matmul serving path."""

from __future__ import annotations

import functools

import jax

from .ref import quantize_weights_ref
from .wq_matmul import (wq_matmul_pallas, wqt_matmul_a8_pallas,
                        wqt_matmul_pallas)


def pack_weight(w, block_k: int = 128, bits: int = 4):
    """(K, N) fp -> (codes, scales) in the kernel layout."""
    return quantize_weights_ref(w, block_k, bits)


@functools.partial(jax.jit, static_argnames=("block_k", "bits",
                                             "tile_m", "tile_n"))
def wq_matmul(x, codes, scales, block_k: int = 128, bits: int = 4,
              tile_m: int = 128, tile_n: int = 128):
    """x (M, K) @ dequant(codes, scales); the M edge (ragged decode
    batches) is padded to the tile grid inside the pallas wrapper."""
    return wq_matmul_pallas(x, codes, scales, block_k=block_k,
                            int4=(bits == 4), tile_m=tile_m, tile_n=tile_n)


@functools.partial(jax.jit, static_argnames=("block_k", "bits",
                                             "tile_m", "tile_n"))
def wqt_matmul(x, codes, scales, block_k: int = -1, bits: int = 8,
               tile_m: int = 128, tile_n: int = 128):
    """x (M, K) @ dequant(codes (N, K[/2]), scales)^T — the QTensor
    (out-major storage) serving entry point.  ``block_k=-1`` = per-tensor
    (1, 1) scale; otherwise blockwise (N, K//bs) scales.  M/N edges are
    padded internally."""
    return wqt_matmul_pallas(x, codes, scales, block_k=block_k,
                             int4=(bits == 4), tile_m=tile_m, tile_n=tile_n)


@functools.partial(jax.jit, static_argnames=("block_k", "bits",
                                             "tile_m", "tile_n"))
def wqt_matmul_a8(xq, xs, codes, scales, block_k: int = -1, bits: int = 8,
                  tile_m: int = 128, tile_n: int = 128):
    """W4A8/W8A8 entry point: per-row int8 activation codes ``xq``
    (M, K) + fp32 row scales ``xs`` (M, 1) against out-major quantized
    weights — the MXU contraction runs int8 x int[4|8] -> int32 with a
    dequant-free fp32 scale epilogue.  Returns fp32 (M, N)."""
    return wqt_matmul_a8_pallas(xq, xs, codes, scales, block_k=block_k,
                                int4=(bits == 4), tile_m=tile_m,
                                tile_n=tile_n)
