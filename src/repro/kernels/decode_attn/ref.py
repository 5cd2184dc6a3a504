"""jnp oracle for the fused quantized decode-attention kernel.

One decode step of GQA attention against a quantized ring-buffer KV
cache, written as the *dense* (non-streaming) computation the Pallas
kernel must reproduce: unpack int4 nibbles / read int8 codes, fold the
per-(slot, kv-head) dequant scale into the score/prob tensors, apply the
ring-validity mask (with optional sliding window) and optional logit
softcap, softmax, and contract with the dequantized values.

The math here is line-for-line the quantized fallback branch of
``repro.models.layers.attn_decode`` — the oracle pins the layer
semantics, the kernel is checked against the oracle, and the layer's
jnp fallback is checked against both.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray
NEG_INF = -1e30


def unpack_int4_ref(packed: Array) -> Array:
    """uint8 (..., hd/2) -> int8 (..., hd); low nibble = even index,
    sign-extended symmetric [-7, 7] nibbles (the kv_quantize layout)."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(packed.shape[:-1] + (2 * packed.shape[-1],))


def ring_validity(pos: Array, cache_len: int,
                  window: Optional[int]) -> Array:
    """(b, cache_len) bool: ring slot j of a row at position ``pos``
    holds absolute position ``p_j`` = the largest p <= pos with
    ``p % cache_len == j``; the slot is a real key iff ``p_j >= 0``
    (and, for sliding-window layers, ``pos - p_j < window``)."""
    j = jnp.arange(cache_len)
    p_j = pos[:, None] - ((pos[:, None] - j[None, :]) % cache_len)
    valid = p_j >= 0
    if window is not None:
        valid &= (pos[:, None] - p_j) < window
    return valid


def decode_attn_ref(q: Array, k_codes: Array, k_scale: Array,
                    v_codes: Array, v_scale: Array, pos: Array, *,
                    bits: int = 8, window: Optional[int] = None,
                    softcap: Optional[float] = None) -> Array:
    """One decode step of quantized-cache GQA attention.

    q:        (b, g, rep, hd) rotated queries (rep = n_heads // g)
    k_codes:  (b, L, g, hd) int8, or (b, L, g, hd/2) uint8 packed int4
    k_scale:  (b, L, g, 1) fp32 per-(slot, kv-head) absmax scales
    v_codes / v_scale: same layout for values
    pos:      (b,) int32 per-row absolute positions (ragged)

    Returns (b, g, rep, hd) in q.dtype.
    """
    b, g, rep, hd = q.shape
    L = k_codes.shape[1]
    if bits == 4:
        k = unpack_int4_ref(k_codes)
        v = unpack_int4_ref(v_codes)
    else:
        k, v = k_codes, v_codes
    # codes contract in the activation dtype, scales fold into the small
    # fp32 score tensor — the attn_decode fallback's exact op order
    s = jnp.einsum("bgrd,blgd->bgrl", q, k.astype(q.dtype))
    scale_t = k_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]  # (b,g,1,l)
    logits = (s.astype(jnp.float32) * scale_t) / np.sqrt(hd)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    valid = ring_validity(pos, L, window)
    bias = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]     # (b,1,1,l)
    probs = jax.nn.softmax(logits + bias, axis=-1)
    p = probs * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bgrl,blgd->bgrd", p.astype(q.dtype),
                      v.astype(q.dtype))


def gather_pool_ref(codes: Array, scale: Array, block_tables: Array,
                    layer, g: int):
    """Layer ``layer`` of a stacked pool stored in the kernel's layout
    (codes (r, n_blocks, bs, g*x), scales (r, n_blocks, g, bs)) + tables
    (b, bps) -> the dense per-row ring view (codes (b, bps*bs, g, x),
    scales (b, bps*bs, g, 1)) the paged kernel must reproduce reads over."""
    c = codes[layer][block_tables]               # (b, bps, bs, g*x)
    s = scale[layer][block_tables]               # (b, bps, g, bs)
    b, bps, bs = c.shape[:3]
    return (c.reshape(b, bps * bs, g, -1),
            jnp.swapaxes(s, 2, 3).reshape(b, bps * bs, g, 1))


def decode_attn_paged_ref(q: Array, k_codes: Array, k_scale: Array,
                          v_codes: Array, v_scale: Array,
                          block_tables: Array, pos: Array, layer, *,
                          bits: int = 8, window: Optional[int] = None,
                          softcap: Optional[float] = None) -> Array:
    """Oracle for the paged kernel: gather each row's blocks of layer
    ``layer`` into the dense ring layout, then run the EXACT dense-ring
    oracle on the view.  Codes/scales live in a shared pool stacked over
    layers (see :func:`gather_pool_ref`) indexed by int32 ``block_tables``
    (b, bps); everything else is unchanged — paged attention IS ring
    attention over a scattered address space.
    """
    g = q.shape[1]
    kc, ks = gather_pool_ref(k_codes, k_scale, block_tables, layer, g)
    vc, vs = gather_pool_ref(v_codes, v_scale, block_tables, layer, g)
    return decode_attn_ref(q, kc, ks, vc, vs, pos, bits=bits,
                           window=window, softcap=softcap)
