"""Public entry point for the fused quantized decode-attention kernel.

Same dispatch rule as ``wq_matmul``/``opt_step``: compiled Pallas on
TPU, interpret mode elsewhere (``repro.kernels.resolve_interpret``), so
CPU CI exercises the identical kernel dataflow.  The wrapper is jitted
with the geometry-independent knobs static; callers route through
``models/layers.py::attn_decode``, which consults the ``use_kernel``
auto-default before getting here.
"""

from __future__ import annotations

import functools

import jax

from .decode_attn import decode_attn_paged_pallas, decode_attn_pallas


@functools.partial(jax.jit,
                   static_argnames=("bits", "window", "softcap", "block_l"))
def decode_attn(q, k_codes, k_scale, v_codes, v_scale, pos, *,
                bits: int = 8, window=None, softcap=None,
                block_l: int = 256):
    """One fused decode step: q (b, g, rep, hd) against an int8 /
    packed-int4 ring KV cache (codes (b, L, g, hd[/2]), scales
    (b, L, g, 1), per-row positions (b,)) -> (b, g, rep, hd)."""
    return decode_attn_pallas(q, k_codes, k_scale, v_codes, v_scale, pos,
                              bits=bits, window=window, softcap=softcap,
                              block_l=block_l)


@functools.partial(jax.jit,
                   static_argnames=("bits", "window", "softcap"))
def decode_attn_paged(q, k_codes, k_scale, v_codes, v_scale,
                      block_tables, pos, *,
                      bits: int = 8, window=None, softcap=None):
    """One fused decode step against the PAGED pool: q (b, g, rep, hd),
    pool codes (n_blocks, bs, g, hd[/2]) + scales (n_blocks, bs, g, 1)
    shared by all rows, int32 ``block_tables`` (b, bps), per-row
    positions (b,) -> (b, g, rep, hd)."""
    return decode_attn_paged_pallas(
        q, k_codes, k_scale, v_codes, v_scale, block_tables, pos,
        bits=bits, window=window, softcap=softcap)
