"""Shared transformer layers: norms, RoPE, GQA attention (full / chunked /
decode, sliding-window, logit-softcap, QK-norm), MLPs, MoE.

All layers are *functional*: ``init_*`` returns a params pytree,
``apply`` functions take (params, inputs).  Compute dtype is the dtype of
the incoming activations; params are stored fp32 (master) and cast by the
caller (mixed-precision policy lives in the train loop).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.qtensor import QTensor, kernel_enabled
from repro.core.qtensor import matmul as _qt_matmul

Array = jnp.ndarray
NEG_INF = -1e30


def matmul(x: Array, w) -> Array:
    """Central weight-matmul dispatch: ``x @ w``.

    ``w`` is either a dense (..., K, N) array (cast to the activation
    dtype, the mixed-precision rule every layer used inline before) or a
    :class:`~repro.core.qtensor.QTensor` stored out-major (N, K), routed
    through the ``wq_matmul`` Pallas kernel (dequant-in-VMEM) or its
    bit-compatible jnp oracle per the kernel auto-default.  Every weight
    matmul in the model goes through here so quantized-storage serving is
    a parameter-tree property, not a model rewrite.
    """
    if isinstance(w, QTensor):
        return _qt_matmul(x, w).astype(x.dtype)
    return x @ w.astype(x.dtype)


def _norm_init(d):
    return {"norm_scale": jnp.ones((d,), jnp.float32)}


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def dense_init(key, d_in, d_out, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA) — init
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None        # sliding-window size (local layers)
    softcap: Optional[float] = None     # gemma2-style logit soft-capping
    qk_norm: bool = False               # gemma3-style per-head RMS on q/k
    is_cross: bool = False              # KV from encoder context (VLM)

    @property
    def q_dim(self):
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self):
        return self.n_kv_heads * self.head_dim


def attn_init(key, spec: AttnSpec) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], spec.d_model, spec.q_dim),
        "wk": dense_init(ks[1], spec.d_model, spec.kv_dim),
        "wv": dense_init(ks[2], spec.d_model, spec.kv_dim),
        "wo": dense_init(ks[3], spec.q_dim, spec.d_model),
    }
    if spec.qk_norm:
        p["q_norm_scale"] = jnp.ones((spec.head_dim,), jnp.float32)
        p["k_norm_scale"] = jnp.ones((spec.head_dim,), jnp.float32)
    return p


def _qkv(params, spec: AttnSpec, x: Array, ctx: Optional[Array] = None):
    """Project q from x, k/v from ctx (cross) or x (self)."""
    b = x.shape[0]
    src = ctx if spec.is_cross else x
    q = matmul(x, params["wq"]).reshape(b, x.shape[1], spec.n_heads, spec.head_dim)
    k = matmul(src, params["wk"]).reshape(b, src.shape[1], spec.n_kv_heads, spec.head_dim)
    v = matmul(src, params["wv"]).reshape(b, src.shape[1], spec.n_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = rms_norm(q, params["q_norm_scale"])
        k = rms_norm(k, params["k_norm_scale"])
    return q, k, v


def _expand_kv(k: Array, n_heads: int) -> Array:
    """(b, l, kvh, d) -> (b, l, h, d) by repeating groups."""
    b, l, kvh, d = k.shape
    rep = n_heads // kvh
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def _mask_bias(q_pos: Array, k_pos: Array, causal: bool, window: Optional[int]) -> Array:
    """(..., q_len, k_len) additive mask bias from absolute positions.

    Positions may be flat ``(len,)`` (shared across the batch — training)
    or per-row ``(b, len)`` (ragged left-padded prompts).  Negative
    positions denote left-pad slots and are always masked as keys, so a
    padded prompt attends exactly what the unpadded prompt would — the
    invariant that makes per-slot prefill-insert match static batching.
    """
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = k_pos[..., None, :] >= 0
    if causal:
        ok = ok & (d >= 0)
    if window is not None:
        ok = ok & (d < window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, softcap):
    """Scores in fp32; q,k,v: (b, l/h-layout below)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attn_apply(
    params,
    spec: AttnSpec,
    x: Array,
    positions: Array,
    ctx: Optional[Array] = None,
    causal: bool = True,
    chunk: Optional[int] = None,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill).

    ``chunk`` enables online-softmax streaming over KV blocks (memory-safe
    for 32k prefill without a quadratic score buffer of the full length).
    ``return_kv`` additionally returns the rotated (k, v) so prefill fills
    the decode cache without re-projecting.
    """
    b, l, _ = x.shape
    q, k, v = _qkv(params, spec, x, ctx)
    if not spec.is_cross:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    kv = (k, v)
    k_pos = positions if not spec.is_cross else jnp.arange(k.shape[1])

    if chunk is None or k.shape[1] <= chunk:
        ke = _expand_kv(k, spec.n_heads)
        ve = _expand_kv(v, spec.n_heads)
        bias = _mask_bias(positions, k_pos, causal and not spec.is_cross, spec.window)
        if bias.ndim == 3:          # per-row positions: (b, q, k) -> (b, 1, q, k)
            bias = bias[:, None]
        o = _sdpa(q, ke, ve, bias, spec.softcap)
    else:
        o = _streaming_sdpa(q, k, v, positions, k_pos,
                            causal and not spec.is_cross, spec.window,
                            spec.softcap, chunk)
    o = o.reshape(b, l, spec.q_dim)
    out = matmul(o, params["wo"])
    return (out, kv) if return_kv else out


def _streaming_sdpa(q, k, v, q_pos, k_pos, causal, window, softcap, chunk):
    """Online-softmax over KV chunks (flash-attention dataflow in pure jnp).

    GQA-native: k/v keep their ``g`` KV heads (never expanded to n_heads —
    the expansion is a (rep)x memory multiplier at 32k).  The scan runs
    over the chunk INDEX with in-body dynamic slicing of the loop-invariant
    k/v, so no transposed stacked copy of the KV is materialized.  State:
    (running max m, running denom s, running out o); peak extra memory is
    one (b, g, rep, q_len, chunk) score tile.
    """
    b, ql, h, d = q.shape
    g = k.shape[2]
    rep = h // g
    kl = k.shape[1]
    n_chunks = (kl + chunk - 1) // chunk
    pad = n_chunks * chunk - kl
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad as FUTURE positions so the causal mask excludes them even
        # when window is None
        k_pos = jnp.pad(k_pos, ((0, 0),) * (k_pos.ndim - 1) + ((0, pad),),
                        constant_values=10 ** 9)
    q4 = q.reshape(b, ql, g, rep, d)
    scale = 1.0 / np.sqrt(d)

    def step(carry, i):
        m, s, o = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
        kpc = jax.lax.dynamic_slice_in_dim(k_pos, i * chunk, chunk,
                                           axis=k_pos.ndim - 1)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", q4, kc).astype(jnp.float32)
        logits = logits * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        bias = _mask_bias(q_pos, kpc, causal, window)
        # padded slots carry sentinel positions: mask even when non-causal
        bias = jnp.where(kpc[..., None, :] >= 10 ** 9, NEG_INF, bias)
        # (q, k) -> (1, 1, 1, q, k) / per-row (b, q, k) -> (b, 1, 1, q, k)
        bias = bias[:, None, None] if bias.ndim == 3 else bias[None, None, None]
        logits = logits + bias
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        s_new = s * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, s_new, o_new), None

    m0 = jnp.full((b, g, rep, ql), NEG_INF, jnp.float32)
    s0 = jnp.zeros((b, g, rep, ql), jnp.float32)
    o0 = jnp.zeros((b, g, rep, ql, d), jnp.float32)
    from repro.distributed.context import scan_unroll
    (m, s, o), _ = jax.lax.scan(jax.checkpoint(step, prevent_cse=False),
                                (m0, s0, o0), jnp.arange(n_chunks),
                                unroll=scan_unroll(n_chunks))
    o = o / jnp.maximum(s, 1e-30)[..., None]
    # (b, g, rep, q, d) -> (b, q, h, d)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, ql, h, d).astype(q.dtype)


# ---- quantized KV cache (per-vector absmax; beyond-paper serving feature
# using the paper's own quantizer).  int8 halves the decode cache traffic;
# int4 (symmetric [-7, 7] nibbles packed two-per-byte along head_dim)
# quarters it — pairing with int4 weights so the WHOLE decode working set
# streams at <= 0.5 byte/element.

def kv_bits(kv_quant) -> int:
    """Normalize the ``kv_quant`` option: False/None -> 0 (dense),
    True/'int8' -> 8, 'int4' -> 4."""
    if not kv_quant:
        return 0
    if kv_quant is True or kv_quant == "int8":
        return 8
    if kv_quant == "int4":
        return 4
    raise ValueError(f"kv_quant must be False, True, 'int8' or 'int4'; "
                     f"got {kv_quant!r}")


def _pack_int4(codes: Array) -> Array:
    """int8 (..., hd) in [-7, 7] -> uint8 (..., hd/2); low nibble = even
    index."""
    lo = codes[..., 0::2] & 0xF
    hi = codes[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_int4(packed: Array) -> Array:
    """uint8 (..., hd/2) -> int8 (..., hd) (sign-extended nibbles)."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(packed.shape[:-1] + (2 * packed.shape[-1],))


def kv_quantize(k: Array, bits: int = 8) -> Dict[str, Array]:
    """k: (b, l, kvh, hd) -> codes + fp32 scale per (b, l, kvh).

    ``bits=8``: int8 codes.  ``bits=4``: int4 codes packed two-per-byte
    along head_dim (requires even head_dim)."""
    qmax = {8: 127.0, 4: 7.0}[bits]
    absmax = jnp.max(jnp.abs(k), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, jnp.ones_like(absmax))
    codes = jnp.clip(jnp.rint(k / scale), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        if k.shape[-1] % 2:
            raise ValueError(f"int4 KV cache needs even head_dim, "
                             f"got {k.shape[-1]}")
        codes = _pack_int4(codes)
    return {"codes": codes, "scale": scale.astype(jnp.float32)}


def _is_quantized_cache(c) -> bool:
    return isinstance(c, dict) and "codes" in c


def _cache_codes(c) -> Array:
    """Quantized-cache codes as int8, unpacking int4 nibbles (uint8
    storage marks the packed layout)."""
    codes = c["codes"]
    return _unpack_int4(codes) if codes.dtype == jnp.uint8 else codes


def _cache_write(cache, new, slot, bidx):
    """Write the (b, kvh, hd) vector `new` at ring slots."""
    with jax.named_scope(spans.KV_CACHE):
        if _is_quantized_cache(cache):
            bits = 4 if cache["codes"].dtype == jnp.uint8 else 8
            q = kv_quantize(new[:, None], bits)  # (b,1,kvh,*)
            return {
                "codes": cache["codes"].at[bidx, slot].set(q["codes"][:, 0]),
                "scale": cache["scale"].at[bidx, slot].set(q["scale"][:, 0]),
            }
        return cache.at[bidx, slot].set(new.astype(cache.dtype))


def attn_decode(
    params,
    spec: AttnSpec,
    x: Array,                      # (b, 1, d_model) — one new token
    pos: Array,                    # (b,) int32 current position
    cache_k,                       # (b, cache_len, kvh, hd) or quantized dict
    cache_v,
    cross_kv: Optional[Tuple[Array, Array]] = None,
):
    """Single-token decode against a KV cache.

    Grouped-query einsums throughout: the KV cache is NEVER expanded to
    n_heads (at 32k x 128-batch that expansion would dominate HBM).  For
    int8-quantized caches the dequant scale is folded into the small
    per-head score/prob tensors, so the big code tensor is read once as
    int8 and converted inside the contraction.

    Self-attn K/V is written at ``pos % cache_len`` (ring buffer for
    sliding-window layers; cache_len == max_seq for global layers).
    Returns (out, new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    g = spec.n_kv_heads
    rep = spec.n_heads // g
    hd = spec.head_dim

    def scores_from(q4, ck, ck_dense=None):
        """q4: (b, g, rep, hd); ck raw (b,l,g,hd) or quantized.
        ``ck_dense`` is the hoisted one-per-step unpack of a quantized
        cache (int4 nibble unpacking must not be re-traced per use)."""
        if _is_quantized_cache(ck):
            codes = ck_dense if ck_dense is not None else _cache_codes(ck)
            s = jnp.einsum("bgrd,blgd->bgrl", q4, codes.astype(q4.dtype))
            return s.astype(jnp.float32) * ck["scale"][..., 0].transpose(
                0, 2, 1)[:, :, None, :]
        return jnp.einsum("bgrd,blgd->bgrl", q4,
                          ck.astype(q4.dtype)).astype(jnp.float32)

    def out_from(probs, cv, cv_dense=None):
        """probs: (b, g, rep, l) fp32; cv raw or quantized -> (b,g,rep,hd)."""
        if _is_quantized_cache(cv):
            codes = cv_dense if cv_dense is not None else _cache_codes(cv)
            p = probs * cv["scale"][..., 0].transpose(0, 2, 1)[:, :, None, :]
            return jnp.einsum("bgrl,blgd->bgrd", p.astype(x.dtype),
                              codes.astype(x.dtype))
        return jnp.einsum("bgrl,blgd->bgrd", probs.astype(x.dtype),
                          cv.astype(x.dtype))

    if spec.is_cross:
        q = matmul(x, params["wq"]).reshape(b, spec.n_heads, hd)
        if spec.qk_norm:
            q = rms_norm(q, params["q_norm_scale"])
        q4 = q.reshape(b, g, rep, hd)
        k, v = cross_kv
        logits = scores_from(q4, k) / np.sqrt(hd)
        if spec.softcap is not None:
            logits = spec.softcap * jnp.tanh(logits / spec.softcap)
        probs = jax.nn.softmax(logits, axis=-1)
        o = out_from(probs, v).reshape(b, 1, spec.q_dim)
        return matmul(o, params["wo"]), cache_k, cache_v

    q, k, v = _qkv(params, spec, x)
    q = apply_rope(q, pos[:, None], spec.rope_theta)
    k = apply_rope(k, pos[:, None], spec.rope_theta)
    cache_len = (cache_k["codes"] if _is_quantized_cache(cache_k)
                 else cache_k).shape[1]
    slot = (pos % cache_len).astype(jnp.int32)
    bidx = jnp.arange(b)
    cache_k = _cache_write(cache_k, k[:, 0], slot, bidx)
    cache_v = _cache_write(cache_v, v[:, 0], slot, bidx)

    q4 = q.reshape(b, g, rep, hd)
    if _is_quantized_cache(cache_k) and kernel_enabled():
        # fused path: the Pallas kernel reads the packed codes from HBM
        # once and does unpack + dequant + QK^T + online softmax + PV in
        # VMEM — the decode program never materializes a dense cache
        from repro.kernels.decode_attn import decode_attn
        bits = 4 if cache_k["codes"].dtype == jnp.uint8 else 8
        o = decode_attn(q4, cache_k["codes"], cache_k["scale"],
                        cache_v["codes"], cache_v["scale"], pos,
                        bits=bits, window=spec.window, softcap=spec.softcap)
        o = o.reshape(b, 1, spec.q_dim)
        return matmul(o, params["wo"]), cache_k, cache_v

    # jnp fallback: for quantized caches, unpack int4 nibbles ONCE per
    # step per layer here (k and v each), never per score/prob chunk —
    # tests pin the unpack count at the jaxpr level
    k_dense = _cache_codes(cache_k) if _is_quantized_cache(cache_k) \
        else cache_k
    v_dense = _cache_codes(cache_v) if _is_quantized_cache(cache_v) \
        else cache_v
    logits = scores_from(q4, cache_k, k_dense) / np.sqrt(hd)
    if spec.softcap is not None:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)
    # ring-slot validity: slot j holds absolute position p_j = the largest
    # p <= pos with p % cache_len == j; valid iff p_j >= 0 (and within the
    # sliding window for local layers).
    j = jnp.arange(cache_len)
    p_j = pos[:, None] - ((pos[:, None] - j[None, :]) % cache_len)
    valid = p_j >= 0
    if spec.window is not None:
        valid &= (pos[:, None] - p_j) < spec.window
    bias = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]  # (b,1,1,l)
    probs = jax.nn.softmax(logits + bias, axis=-1)
    o = out_from(probs, cache_v, v_dense).reshape(b, 1, spec.q_dim)
    return matmul(o, params["wo"]), cache_k, cache_v


# ---- paged KV (DESIGN.md §13): the per-slot dense ring is replaced by a
# shared pool of `block_size`-token blocks plus an int32 block table per
# slot.  The logical ring layout is unchanged — table entry i of a slot
# holds ring slots [i*bs, (i+1)*bs) — so ring validity, RoPE positions and
# the quantizer are byte-compatible with the dense path; only the physical
# address of a slot's KV moves (and can be shared across tables).  Pool
# leaves stay stacked over the layer scan's repeats and every access names
# its layer: a quantized pool is stored in the decode kernel's layout
# (``lm.init_paged_pool``: codes (r, n_blocks, bs, g*hd_c), scales
# (r, n_blocks, g, bs)), a dense one as (r, n_blocks, bs, g, hd).

def paged_gather(pool, bt, layer):
    """Gather layer ``layer``'s pool blocks into a dense per-row view.

    ``pool``: stacked pool leaf (dense or codes/scale dict); ``bt``: int32
    block table ``(b, bps)``.  Returns the ``(b, bps*bs, kvh, ...)`` view
    (quantized: codes ``(b, L, kvh, hd_c)``, scales ``(b, L, kvh, 1)``)
    whose entries are byte-identical to what the dense-ring cache of the
    same requests would hold.  Only the gathered rows are re-laid out.
    """
    with jax.named_scope(spans.KV_CACHE):
        if _is_quantized_cache(pool):
            codes = pool["codes"][layer, bt]         # (b, bps, bs, C)
            scale = pool["scale"][layer, bt]         # (b, bps, g, bs)
            b, bps, g, bs = scale.shape
            return {"codes": codes.reshape(b, bps * bs, g, -1),
                    "scale": jnp.swapaxes(scale, 2, 3).reshape(
                        b, bps * bs, g, 1)}
        out = pool[layer, bt]                        # (b, bps, bs, g, hd)
        return out.reshape((out.shape[0], out.shape[1] * out.shape[2])
                           + out.shape[3:])


def _pool_write(pool, new, layer, bid, off):
    """Write each row's (kvh, hd) vector ``new[i]`` into layer ``layer``
    of the stacked pool at block ``bid[i]``, offset ``off[i]``, where it
    lies: one token per row, nothing else of the pool is touched.  Rows
    whose table entry is 0 (cleared/idle) land in the reserved dump
    block."""
    with jax.named_scope(spans.KV_CACHE):
        if _is_quantized_cache(pool):
            bits = 4 if pool["codes"].dtype == jnp.uint8 else 8
            q = kv_quantize(new[:, None], bits)      # (b, 1, kvh, *)
            b = new.shape[0]
            return {
                "codes": pool["codes"].at[layer, bid, off].set(
                    q["codes"].reshape(b, -1)),
                "scale": pool["scale"].at[layer, bid, :, off].set(
                    q["scale"].reshape(b, -1)),
            }
        return pool.at[layer, bid, off].set(new.astype(pool.dtype))


def attn_decode_paged(
    params,
    spec: AttnSpec,
    x: Array,                      # (b, 1, d_model) — one new token
    pos: Array,                    # (b,) int32 current position
    pool_k,                        # stacked pool leaf (dense or dict)
    pool_v,
    block_tables: Array,           # (b, bps) int32 pool block ids
    layer: Array,                  # int32 scalar: the layer scan's repeat
):
    """Single-token decode of layer ``layer`` against the stacked PAGED KV
    pool.

    The new K/V is quantized and written through the block table into
    the pool where it lies (same per-vector quantizer, same ring slot ->
    same bytes as the dense path), then scored either by the
    layer-indexed, block-table-indexed Pallas kernel, which streams the
    blocks from the stacked pool, or by gathering the row's blocks into a
    dense view and running the exact dense-ring fallback math on it —
    op-for-op identical to :func:`attn_decode`'s fallback, so greedy
    outputs cannot drift.  Returns (out, new_pool_k, new_pool_v).
    """
    b = x.shape[0]
    g = spec.n_kv_heads
    rep = spec.n_heads // g
    hd = spec.head_dim
    quantized = _is_quantized_cache(pool_k)
    block_size = (pool_k["codes"] if quantized else pool_k).shape[2]
    bps = block_tables.shape[1]
    cache_len = bps * block_size

    q, k, v = _qkv(params, spec, x)
    q = apply_rope(q, pos[:, None], spec.rope_theta)
    k = apply_rope(k, pos[:, None], spec.rope_theta)
    slot = (pos % cache_len).astype(jnp.int32)
    bid = jnp.take_along_axis(block_tables, (slot // block_size)[:, None],
                              axis=1)[:, 0]
    off = slot % block_size
    pool_k = _pool_write(pool_k, k[:, 0], layer, bid, off)
    pool_v = _pool_write(pool_v, v[:, 0], layer, bid, off)

    q4 = q.reshape(b, g, rep, hd)
    if quantized and kernel_enabled():
        # fused path: the kernel's grid walks the block table and streams
        # each block's packed codes from HBM exactly once
        from repro.kernels.decode_attn import decode_attn_paged
        bits = 4 if pool_k["codes"].dtype == jnp.uint8 else 8
        o = decode_attn_paged(q4, pool_k["codes"], pool_k["scale"],
                              pool_v["codes"], pool_v["scale"],
                              block_tables, pos, layer, bits=bits,
                              window=spec.window, softcap=spec.softcap)
        o = o.reshape(b, 1, spec.q_dim)
        return matmul(o, params["wo"]), pool_k, pool_v

    # jnp fallback: gather the row's blocks into the dense-ring view and
    # run the EXACT attn_decode fallback ops on it (same hoisted
    # unpack-once discipline, same validity formula) — bit-identical to
    # the dense scheduler by construction
    ck = paged_gather(pool_k, block_tables, layer)
    cv = paged_gather(pool_v, block_tables, layer)
    k_dense = _cache_codes(ck) if _is_quantized_cache(ck) else ck
    v_dense = _cache_codes(cv) if _is_quantized_cache(cv) else cv
    if _is_quantized_cache(ck):
        s = jnp.einsum("bgrd,blgd->bgrl", q4, k_dense.astype(q4.dtype))
        logits = s.astype(jnp.float32) * ck["scale"][..., 0].transpose(
            0, 2, 1)[:, :, None, :]
    else:
        logits = jnp.einsum("bgrd,blgd->bgrl", q4,
                            k_dense.astype(q4.dtype)).astype(jnp.float32)
    logits = logits / np.sqrt(hd)
    if spec.softcap is not None:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)
    j = jnp.arange(cache_len)
    p_j = pos[:, None] - ((pos[:, None] - j[None, :]) % cache_len)
    valid = p_j >= 0
    if spec.window is not None:
        valid &= (pos[:, None] - p_j) < spec.window
    bias = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]  # (b,1,1,l)
    probs = jax.nn.softmax(logits + bias, axis=-1)
    if _is_quantized_cache(cv):
        p = probs * cv["scale"][..., 0].transpose(0, 2, 1)[:, :, None, :]
        o = jnp.einsum("bgrl,blgd->bgrd", p.astype(x.dtype),
                       v_dense.astype(x.dtype))
    else:
        o = jnp.einsum("bgrl,blgd->bgrd", probs.astype(x.dtype),
                       v_dense.astype(x.dtype))
    o = o.reshape(b, 1, spec.q_dim)
    return matmul(o, params["wo"]), pool_k, pool_v


def attn_chunk_apply(
    params,
    spec: AttnSpec,
    x: Array,                      # (b, cw, d_model) — one prompt chunk
    positions: Array,              # (b, cw) absolute positions (start + col)
    chunk_lens: Array,             # (b,) real tokens this chunk (rest pad)
    cache_k,                       # (b, L, kvh, hd) or quantized dict
    cache_v,
):
    """Chunked-prefill attention: a block of new prompt tokens against a
    partial KV cache (DESIGN.md §8).

    Chunk queries attend the UNION of (a) the pre-chunk cache — slot
    validity derived from ``start - 1`` exactly as decode derives it from
    ``pos`` — and (b) the in-chunk fresh keys under the causal/window
    mask.  Scoring against the *pre-write* cache plus fresh arrays (not
    the post-write ring) is what keeps sliding-window layers exact: a
    late chunk token may ring-evict a slot an earlier query still needs,
    but that key is still present as a fresh array here.  The chunk's K/V
    are scatter-written afterwards at ``pos % L`` (only each slot's
    newest in-chunk position — duplicates masked to a dump row), so the
    resulting cache is byte-identical to what per-token decode writes
    would have left.

    Rows are right-padded to the fixed chunk width: pad columns are
    masked as keys, dumped as writes, and their (garbage) outputs are
    ignored by the caller.  Returns (out (b, cw, d_model), new_cache_k,
    new_cache_v).
    """
    b, cw, _ = x.shape
    g = spec.n_kv_heads
    rep = spec.n_heads // g
    hd = spec.head_dim

    if spec.is_cross:
        # cross-attn KV is position-free encoder context: plain
        # (non-causal) attention over the cached keys, no cache update
        q = matmul(x, params["wq"]).reshape(b, cw, spec.n_heads, hd)
        if spec.qk_norm:
            q = rms_norm(q, params["q_norm_scale"])
        ke = _expand_kv(cache_k.astype(x.dtype), spec.n_heads)
        ve = _expand_kv(cache_v.astype(x.dtype), spec.n_heads)
        bias = jnp.zeros((1, 1, 1, ke.shape[1]), jnp.float32)
        o = _sdpa(q, ke, ve, bias, spec.softcap)
        return (matmul(o.reshape(b, cw, spec.q_dim), params["wo"]),
                cache_k, cache_v)

    q, k, v = _qkv(params, spec, x)
    q = apply_rope(q, positions, spec.rope_theta)
    k = apply_rope(k, positions, spec.rope_theta)
    L = (cache_k["codes"] if _is_quantized_cache(cache_k)
         else cache_k).shape[1]
    q4 = q.reshape(b, cw, g, rep, hd)
    scale = 1.0 / np.sqrt(hd)

    # (a) scores against the pre-chunk cache: slot j holds absolute
    # position p_j = the largest p <= start-1 with p % L == j (decode's
    # ring-validity rule anchored at the last pre-chunk position)
    prev_last = positions[:, 0] - 1                                # (b,)
    j = jnp.arange(L)
    p_j = prev_last[:, None] - ((prev_last[:, None] - j[None, :]) % L)

    def cache_scores(ck):
        if _is_quantized_cache(ck):
            s = jnp.einsum("bqgrd,blgd->bgrql", q4,
                           _cache_codes(ck).astype(q4.dtype))
            return s.astype(jnp.float32) * ck["scale"][..., 0].transpose(
                0, 2, 1)[:, :, None, None, :]
        return jnp.einsum("bqgrd,blgd->bgrql", q4,
                          ck.astype(q4.dtype)).astype(jnp.float32)

    def cache_out(probs, cv):
        if _is_quantized_cache(cv):
            p = probs * cv["scale"][..., 0].transpose(
                0, 2, 1)[:, :, None, None, :]
            return jnp.einsum("bgrql,blgd->bqgrd", p.astype(x.dtype),
                              _cache_codes(cv).astype(x.dtype))
        return jnp.einsum("bgrql,blgd->bqgrd", probs.astype(x.dtype),
                          cv.astype(x.dtype))

    ok_c = (p_j >= 0)[:, None, :]                                  # (b, 1, L)
    if spec.window is not None:
        ok_c = ok_c & (positions[:, :, None] - p_j[:, None, :] < spec.window)
    bias_c = jnp.where(ok_c, 0.0, NEG_INF).astype(jnp.float32)

    # (b) causal scores against the in-chunk fresh keys
    kcol_ok = (jnp.arange(cw)[None, :] < chunk_lens[:, None])      # (b, cw)
    d = positions[:, :, None] - positions[:, None, :]
    ok_f = (d >= 0) & kcol_ok[:, None, :]
    if spec.window is not None:
        ok_f = ok_f & (d < spec.window)
    bias_f = jnp.where(ok_f, 0.0, NEG_INF).astype(jnp.float32)
    k4 = k.reshape(b, cw, g, hd)
    logits_f = jnp.einsum("bqgrd,bkgd->bgrqk", q4,
                          k4).astype(jnp.float32)

    logits = jnp.concatenate([cache_scores(cache_k), logits_f], -1) * scale
    if spec.softcap is not None:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)
    bias = jnp.concatenate(
        [jnp.broadcast_to(bias_c[:, None, None], logits.shape[:-1] + (L,)),
         jnp.broadcast_to(bias_f[:, None, None], logits.shape[:-1] + (cw,))],
        -1)
    probs = jax.nn.softmax(logits + bias, axis=-1)
    o = cache_out(probs[..., :L], cache_v) + jnp.einsum(
        "bgrqk,bkgd->bqgrd", probs[..., L:].astype(x.dtype),
        v.reshape(b, cw, g, hd))
    out = matmul(o.reshape(b, cw, spec.q_dim), params["wo"])

    # scatter-write the chunk's K/V at ring slots; per slot only the
    # chunk's NEWEST position lands (older ring-period duplicates and pad
    # columns go to the dump row, which is sliced off)
    last_real = positions[:, 0] + chunk_lens - 1                   # (b,)
    keep = kcol_ok & (positions >= (last_real - L + 1)[:, None])
    slots = jnp.where(keep, positions % L, L).astype(jnp.int32)
    bidx = jnp.arange(b)[:, None]

    def write(cache, vals):
        if _is_quantized_cache(cache):
            bits = 4 if cache["codes"].dtype == jnp.uint8 else 8
            qv = kv_quantize(vals, bits)
            return {
                "codes": jnp.concatenate(
                    [cache["codes"],
                     jnp.zeros((b, 1) + cache["codes"].shape[2:],
                               cache["codes"].dtype)], 1)
                .at[bidx, slots].set(qv["codes"])[:, :L],
                "scale": jnp.concatenate(
                    [cache["scale"],
                     jnp.ones((b, 1) + cache["scale"].shape[2:],
                              jnp.float32)], 1)
                .at[bidx, slots].set(qv["scale"])[:, :L],
            }
        return jnp.concatenate(
            [cache, jnp.zeros((b, 1) + cache.shape[2:], cache.dtype)], 1
        ).at[bidx, slots].set(vals.astype(cache.dtype))[:, :L]

    with jax.named_scope(spans.KV_CACHE):
        return (out, write(cache_k, k4),
                write(cache_v, v.reshape(b, cw, g, hd)))


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLPSpec:
    d_model: int
    d_ff: int
    kind: str = "swiglu"   # swiglu | geglu | gelu


def mlp_init(key, spec: MLPSpec):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], spec.d_model, spec.d_ff),
         "w_down": dense_init(ks[1], spec.d_ff, spec.d_model)}
    if spec.kind in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[2], spec.d_model, spec.d_ff)
    return p


def mlp_apply(params, spec: MLPSpec, x: Array) -> Array:
    up = matmul(x, params["w_up"])
    if spec.kind == "swiglu":
        h = jax.nn.silu(matmul(x, params["w_gate"])) * up
    elif spec.kind == "geglu":
        h = jax.nn.gelu(matmul(x, params["w_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    return matmul(h, params["w_down"])


# --------------------------------------------------------------------------
# MoE (GShard-style dense dispatch; EP-shardable over the expert axis)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int              # per-expert hidden
    n_experts: int
    top_k: int
    kind: str = "swiglu"
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    group_size: int = 2048  # dispatch group: keeps the one-hot O(G*E*C)


def moe_init(key, spec: MoESpec):
    ks = jax.random.split(key, 4)
    e, d, f = spec.n_experts, spec.d_model, spec.d_ff
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, scale=0.02),
        "w_up": jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale,
        "w_down": jax.random.normal(ks[2], (e, f, d), jnp.float32) / np.sqrt(f),
    }
    if spec.kind in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(ks[3], (e, d, f), jnp.float32) * scale
    return p


def _moe_expert_matmul(xin: Array, w) -> Array:
    """Per-expert matmul ``xin (g, e, c, d) @ w (e, d, f) -> (g, e, c, f)``.

    Dense experts stay a single einsum; QTensor experts (stored
    (e, f, d)) route each expert's (g*c, d) slab through the central
    quantized matmul — expert weights are the dominant HBM term of MoE
    decode, so they must stream as codes too.
    """
    if isinstance(w, QTensor):
        g, e, c, d = xin.shape
        xe = xin.transpose(1, 0, 2, 3).reshape(e, g * c, d)
        out = _qt_matmul(xe, w).astype(xin.dtype)
        return out.reshape(e, g, c, -1).transpose(1, 0, 2, 3)
    return jnp.einsum("gecd,edf->gecf", xin, w.astype(xin.dtype))


def moe_apply(params, spec: MoESpec, x: Array,
              token_mask: Optional[Array] = None
              ) -> Tuple[Array, Dict[str, Array]]:
    """Capacity-based top-k dispatch (GShard).  x: (b, l, d) -> (b, l, d).

    Tokens are processed in GROUPS of ``group_size`` (capacity is enforced
    per group): the dense one-hot dispatch is O(G * E * C) per group —
    without grouping it is O(T^2 * k / E), which at 1M-token steps
    materializes multi-TB tensors (§Perf log).  Group dim shards over
    data, expert dim over model (EP); the dispatch/combine einsums lower
    to all-to-alls under GSPMD.  Returns (out, aux) with load-balance
    terms.

    ``token_mask`` (b,) bool — rows excluded from dispatch entirely: they
    consume NO expert capacity and produce zero output.  Continuous-
    batching decode runs with free/retired slots still in the batch; an
    unmasked garbage row would steal capacity from live requests.
    """
    b, l, d = x.shape
    t = b * l
    e = spec.n_experts
    g_sz = min(spec.group_size, t)
    # group count must divide t; fall back to one group per sequence
    if t % g_sz != 0:
        g_sz = l if t % l == 0 else t
    n_g = t // g_sz

    xt = x.reshape(n_g, g_sz, d)
    logits = (xt @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (g, t, e)

    topv, topi = jax.lax.top_k(probs, spec.top_k)                  # (g, t, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    cap = int(np.ceil(g_sz * spec.top_k / e * spec.capacity_factor))
    cap = max(cap, spec.top_k)

    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)              # (g, t, k, e)
    if token_mask is not None:
        tm = jnp.broadcast_to(token_mask[:, None], (b, l)).reshape(n_g, g_sz)
        onehot = onehot * tm[..., None, None].astype(onehot.dtype)
    # position of each (token, choice) within its expert queue (per group);
    # int32 cumsum (bf16 cumsum loses exactness past 256)
    pos_in_e = jnp.cumsum(
        onehot.reshape(n_g, g_sz * spec.top_k, e), axis=1
    ).reshape(n_g, g_sz, spec.top_k, e) - onehot
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                      # (g, t, k)
    keep = pos < cap
    pos = jnp.where(keep, pos, 0).astype(jnp.int32)

    # 0/1 one-hots are exact in bf16: dispatch einsums run in compute dtype
    kept = (onehot * keep[..., None]).astype(x.dtype)              # (g, t, k, e)
    cap_onehot = jax.nn.one_hot(pos, cap, dtype=x.dtype)           # (g, t, k, c)
    dispatch = jnp.einsum("gtke,gtkc->gtec", kept, cap_onehot)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", kept, cap_onehot,
                         topv.astype(x.dtype))

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xt)
    up = _moe_expert_matmul(xin, params["w_up"])
    if spec.kind in ("swiglu", "geglu"):
        gate = _moe_expert_matmul(xin, params["w_gate"])
        act = jax.nn.silu(gate) if spec.kind == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jax.nn.gelu(up)
    eout = _moe_expert_matmul(h, params["w_down"])
    out = jnp.einsum("gtec,gecd->gtd", combine, eout)

    # GShard aux loss: mean fraction of tokens per expert * mean router prob
    me = jnp.mean(jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    ce_ = jnp.mean(probs, axis=(0, 1))
    aux = {"load_balance_loss": e * jnp.sum(me * ce_),
           "router_entropy": -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), -1))}
    return out.reshape(b, l, d), aux
