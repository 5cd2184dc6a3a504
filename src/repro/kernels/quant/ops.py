"""Public jit'd wrappers for the fused quant kernels.

Handles arbitrary tensor ranks (reshape to the kernel's 2-D layout with
lane-aligned padding) and format dispatch (INT-n grids / FP4 e2m1); the
kernel itself picks Mosaic or interpret mode (``resolve_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import CodebookFormat, get_format

from .quant_blockwise import quant_pallas


def _to_2d(w, block_size: int):
    """Flatten to (R, C): C = one-or-more whole blocks, R padded to the
    8-row sublane tile."""
    n = w.size
    c = block_size if block_size > 0 else min(n, 1024)
    c = max(c, 128) if n >= 128 else n
    n_pad = (-n) % c
    flat = w.reshape(-1)
    if n_pad:
        flat = jnp.pad(flat, (0, n_pad))
    w2 = flat.reshape(-1, c)
    r_pad = (-w2.shape[0]) % 8
    if r_pad:
        w2 = jnp.pad(w2, ((0, r_pad), (0, 0)))
        n_pad += r_pad * c
    return w2, n_pad


@functools.partial(jax.jit, static_argnames=("fmt_name", "block_size"))
def quant_rtn(w, fmt_name: str = "int4", block_size: int = 256):
    """Fused blockwise absmax + RTN + dequant.  Any-rank input; blocks run
    along the flattened minor axis (same contract as core.quantize's
    blockwise path)."""
    fmt = get_format(fmt_name)
    fp4 = isinstance(fmt, CodebookFormat)
    qmax = 6.0 if fp4 else float(fmt.qmax)
    shape = w.shape

    if block_size == -1:
        # per-tensor: one cheap absmax pass outside, fused round+dequant in
        absmax = jnp.max(jnp.abs(w))
        scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
        w2, n_pad = _to_2d(w, 1024)
        out = quant_pallas(w2, qmax=qmax, block_size=-1, fp4=fp4,
                           scale=scale)
    else:
        w2, n_pad = _to_2d(w, block_size)
        out = quant_pallas(w2, qmax=qmax, block_size=block_size, fp4=fp4)
    flat = out.reshape(-1)
    if n_pad:
        flat = flat[:-n_pad]
    return flat.reshape(shape)


@functools.partial(jax.jit, static_argnames=("fmt_name", "block_size"))
def quant_rr(w, key, fmt_name: str = "int4", block_size: int = 256):
    """Fused blockwise absmax + unbiased randomized rounding + dequant."""
    fmt = get_format(fmt_name)
    fp4 = isinstance(fmt, CodebookFormat)
    qmax = 6.0 if fp4 else float(fmt.qmax)
    shape = w.shape

    if block_size == -1:
        absmax = jnp.max(jnp.abs(w))
        scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
        w2, n_pad = _to_2d(w, 1024)
        noise = jax.random.uniform(key, w2.shape, dtype=jnp.float32)
        out = quant_pallas(w2, qmax=qmax, block_size=-1, fp4=fp4,
                           noise=noise, scale=scale)
    else:
        w2, n_pad = _to_2d(w, block_size)
        noise = jax.random.uniform(key, w2.shape, dtype=jnp.float32)
        out = quant_pallas(w2, qmax=qmax, block_size=block_size, fp4=fp4,
                           noise=noise)
    flat = out.reshape(-1)
    if n_pad:
        flat = flat[:-n_pad]
    return flat.reshape(shape)
