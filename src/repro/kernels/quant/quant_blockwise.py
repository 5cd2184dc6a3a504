"""Fused blockwise absmax quantization kernels (pl.pallas_call + BlockSpec).

Layout contract: the wrapper (ops.py) reshapes any tensor to 2-D (R, C)
with the quantization block running along the minor (lane) axis — C is a
multiple of the quant block size ``bs`` which itself is a multiple of 128,
so per-block absmax reductions are lane-aligned VREG reductions and the
scale broadcast stays inside the tile.  One HBM round-trip computes
scale + round + dequant (the paper's stock-op version is ~4 passes:
absmax, scale, round, multiply).

Kernels:
  * ``rtn``     — round-to-nearest cast.
  * ``rr``      — unbiased randomized rounding (noise tile passed in:
                  keeps the kernel oracle-exact / interpret-testable;
                  a pltpu PRNG variant can replace it on hardware).
  * both take either in-tile absmax (blockwise) or a precomputed
    per-tensor scale operand (block_size = -1).

Supported formats: symmetric INT-n grids (qmax parameter) and the FP4
e2m1 codebook (unrolled cell comparisons — no gathers on TPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

E2M1_POS = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)


def _block_scales(w, bs: int, qmax: float):
    tm, tn = w.shape
    wb = w.reshape(tm, tn // bs, bs)
    absmax = jnp.max(jnp.abs(wb), axis=-1, keepdims=True)
    s = jnp.where(absmax > 0, absmax / qmax, jnp.ones_like(absmax))
    return wb, s


def _fp4_neighbors(z):
    """(lo, hi) codebook brackets for z in [-6, 6] — unrolled comparisons,
    gather-free (TPU vectorizes compare/select chains)."""
    codes = np.concatenate([-np.array(E2M1_POS[::-1]), np.array(E2M1_POS[1:])])
    lo = jnp.full_like(z, codes[0])
    hi = jnp.full_like(z, codes[0])
    for k in range(len(codes) - 1):
        c0, c1 = float(codes[k]), float(codes[k + 1])
        in_cell = (z >= c0) & (z < c1)
        lo = jnp.where(in_cell, c0, lo)
        hi = jnp.where(in_cell, c1, hi)
    top = z >= float(codes[-1])
    lo = jnp.where(top, float(codes[-1]), lo)
    hi = jnp.where(top, float(codes[-1]), hi)
    return lo, hi


def _round_int(wb, s, qmax, noise=None):
    z = jnp.clip(wb / s, -qmax, qmax)
    if noise is None:
        q = jnp.rint(z)
    else:
        lo = jnp.floor(z)
        q = jnp.clip(lo + (noise < (z - lo)).astype(z.dtype), -qmax, qmax)
    return q * s


def _round_fp4(wb, s, noise=None):
    z = jnp.clip(wb / s, -6.0, 6.0)
    lo, hi = _fp4_neighbors(z)
    if noise is None:
        q = jnp.where(jnp.abs(z - lo) <= jnp.abs(hi - z), lo, hi)
    else:
        gap = hi - lo
        p_hi = jnp.where(gap > 0, (z - lo) / jnp.where(gap > 0, gap, 1.0), 0.0)
        q = jnp.where(noise < p_hi, hi, lo)
    return q * s


def _quant_kernel(w_ref, *refs, qmax, bs, fp4, stochastic):
    if stochastic:
        noise_ref, out_ref = refs
        noise = noise_ref[...]
    else:
        (out_ref,) = refs
        noise = None
    w = w_ref[...].astype(jnp.float32)
    tm, tn = w.shape
    wb, s = _block_scales(w, bs, 6.0 if fp4 else qmax)
    nb = None if noise is None else noise.reshape(tm, tn // bs, bs)
    q = _round_fp4(wb, s, nb) if fp4 else _round_int(wb, s, qmax, nb)
    out_ref[...] = q.reshape(tm, tn).astype(out_ref.dtype)


def _quant_kernel_pretensor(w_ref, s_ref, *refs, qmax, fp4, stochastic):
    if stochastic:
        noise_ref, out_ref = refs
        noise = noise_ref[...]
    else:
        (out_ref,) = refs
        noise = None
    w = w_ref[...].astype(jnp.float32)
    s = s_ref[0, 0]
    if fp4:
        out_ref[...] = _round_fp4(w, s, noise).astype(out_ref.dtype)
    else:
        out_ref[...] = _round_int(w, s, qmax, noise).astype(out_ref.dtype)


def quant_pallas(w2d: jnp.ndarray, *, qmax: float, block_size: int,
                 fp4: bool = False, noise: Optional[jnp.ndarray] = None,
                 scale: Optional[jnp.ndarray] = None,
                 tile_m: int = 8, tile_n: int = 1024,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """w2d: (R, C).  blockwise when ``scale is None`` (block_size | tile_n),
    else per-tensor with the precomputed (1,1) ``scale``."""
    R, C = w2d.shape
    tile_n = min(tile_n, C)
    tile_m = min(tile_m, R)
    assert R % tile_m == 0 and C % tile_n == 0, (R, C, tile_m, tile_n)
    stochastic = noise is not None
    grid = (R // tile_m, C // tile_n)
    tile = pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j))

    if scale is None:
        assert tile_n % block_size == 0, (tile_n, block_size)
        kern = functools.partial(_quant_kernel, qmax=qmax, bs=block_size,
                                 fp4=fp4, stochastic=stochastic)
        in_specs = [tile] + ([tile] if stochastic else [])
        args = (w2d,) + ((noise,) if stochastic else ())
    else:
        kern = functools.partial(_quant_kernel_pretensor, qmax=qmax, fp4=fp4,
                                 stochastic=stochastic)
        in_specs = [tile, pl.BlockSpec((1, 1), lambda i, j: (0, 0))]
        in_specs += [tile] if stochastic else []
        args = (w2d, scale.reshape(1, 1)) + ((noise,) if stochastic else ())

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), w2d.dtype),
        interpret=resolve_interpret(interpret), name="quant_blockwise",
    )(*args)
