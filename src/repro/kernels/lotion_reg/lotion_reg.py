"""Fused LOTION regularizer kernel (pl.pallas_call + BlockSpec).

One pass over (w, fisher) computes BOTH the penalty contribution and its
closed-form gradient:

    var_i  = (hi_i - w_i)(w_i - lo_i)
    pen    = 1/2 sum_i f_i var_i
    grad_i = 1/2 f_i (lo_i + hi_i - 2 w_i)

(the a.e. derivative with stop-gradded scales — paper Eq. 3).  The paper's
stock-op implementation runs ~5 elementwise HBM passes plus an autodiff
re-traversal; this kernel reads w and f once, writes grad once, and
accumulates per-tile penalty partials into a (grid_m, grid_n) output that
the wrapper sums (cheap: one scalar per tile).

Scales: in-tile blockwise absmax (block_size | tile_n) or precomputed
per-tensor scale operand — same layout contract as the quant kernel.
"""

from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.quant.quant_blockwise import _fp4_neighbors

# one penalty partial per grid step, as an (8, 128) f32 VMEM block
PEN_TILE = (8, 128)


def tile_partial(x):
    """``PEN_TILE`` block holding ``sum(x)`` at [0, 0] and zeros elsewhere,
    so summing a kernel's whole partials output gives the exact total."""
    total = jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                    keepdims=True)                       # (1, 1)
    r = jax.lax.broadcasted_iota(jnp.int32, PEN_TILE, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, PEN_TILE, 1)
    return jnp.where((r == 0) & (c == 0), total, 0.0)


def pen_spec_and_shape(grid):
    """BlockSpec + output shape of the per-tile penalty partials."""
    spec = pl.BlockSpec(PEN_TILE, lambda i, j: (i, j))
    shape = jax.ShapeDtypeStruct(
        (grid[0] * PEN_TILE[0], grid[1] * PEN_TILE[1]), jnp.float32)
    return spec, shape


def _neighbors_int(wb, s, qmax):
    z = jnp.clip(wb / s, -qmax, qmax)
    return jnp.floor(z) * s, jnp.ceil(z) * s


def _neighbors_fp4(wb, s):
    z = jnp.clip(wb / s, -6.0, 6.0)
    lo, hi = _fp4_neighbors(z)
    return lo * s, hi * s


def _blockwise_neighbors(w, bs, qmax, fp4):
    """In-tile blockwise absmax scales + (lo, hi) brackets for a (tm, tn)
    tile, blocks of ``bs`` along the lane dim.  THE scale convention for
    every kernel that quantizes in-tile (lotion_reg, opt_step) — one
    definition so the fused step's penalty can never diverge from the
    loss-side regularizer kernel."""
    tm, tn = w.shape
    wb = w.reshape(tm, tn // bs, bs)
    absmax = jnp.max(jnp.abs(wb), axis=-1, keepdims=True)
    denom = 6.0 if fp4 else qmax
    s = jnp.where(absmax > 0, absmax / denom, jnp.ones_like(absmax))
    lo, hi = _neighbors_fp4(wb, s) if fp4 else _neighbors_int(wb, s, qmax)
    return lo.reshape(tm, tn), hi.reshape(tm, tn)


def _reg_kernel(w_ref, f_ref, grad_ref, pen_ref, *, qmax, bs, fp4):
    w = w_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    lo, hi = _blockwise_neighbors(w, bs, qmax, fp4)
    var = (hi - w) * (w - lo)
    grad_ref[...] = (0.5 * f * (lo + hi - 2.0 * w)).astype(grad_ref.dtype)
    pen_ref[...] = tile_partial(0.5 * (f * var))


def _reg_kernel_pretensor(w_ref, f_ref, s_ref, grad_ref, pen_ref, *, qmax, fp4):
    w = w_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    s = s_ref[0, 0]
    lo, hi = _neighbors_fp4(w, s) if fp4 else _neighbors_int(w, s, qmax)
    var = (hi - w) * (w - lo)
    grad_ref[...] = (0.5 * f * (lo + hi - 2.0 * w)).astype(grad_ref.dtype)
    pen_ref[...] = tile_partial(0.5 * (f * var))


def lotion_reg_pallas(w2d, f2d, *, qmax: float, block_size: int,
                      fp4: bool = False, scale=None,
                      tile_m: int = 8, tile_n: int = 1024,
                      interpret: Optional[bool] = None):
    """Returns (grad (R, C), penalty partials); the penalty is the sum of
    the partials array."""
    R, C = w2d.shape
    tile_n = min(tile_n, C)
    tile_m = min(tile_m, R)
    assert R % tile_m == 0 and C % tile_n == 0
    grid = (R // tile_m, C // tile_n)
    tile = pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j))
    pen_spec, pen_shape = pen_spec_and_shape(grid)
    out_shape = (jax.ShapeDtypeStruct((R, C), w2d.dtype), pen_shape)

    if scale is None:
        assert tile_n % block_size == 0
        kern = functools.partial(_reg_kernel, qmax=qmax, bs=block_size, fp4=fp4)
        in_specs = [tile, tile]
        args = (w2d, f2d)
    else:
        kern = functools.partial(_reg_kernel_pretensor, qmax=qmax, fp4=fp4)
        in_specs = [tile, tile, pl.BlockSpec((1, 1), lambda i, j: (0, 0))]
        args = (w2d, f2d, scale.reshape(1, 1))

    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=(tile, pen_spec), out_shape=out_shape,
        interpret=resolve_interpret(interpret), name="lotion_reg",
    )(*args)
