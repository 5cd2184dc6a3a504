"""Stage-based language model supporting all assigned architecture families.

A model is a *unit pattern* of block kinds (e.g. ``("local", "attn")`` for
Gemma-2's alternating layers, ``("mamba",)*6`` for Zamba-2 groups,
``("attn", "attn", "attn", "attn", "xattn")`` for Llama-3.2-Vision) tiled
``n_repeats`` times.  Unit parameters are stacked along a leading repeats
axis and the repeats loop is a single ``jax.lax.scan`` — keeping the HLO
(and compile time on 512-device meshes) proportional to ONE unit, not the
full depth.

Three entry points per model:
* ``forward``  — full-sequence logits (training).
* ``prefill``  — full-sequence forward that also fills the decode cache.
* ``decode``   — one-token step against the cache (serving).

Block kinds: ``attn`` (global self-attn), ``local`` (sliding-window),
``xattn`` (cross-attention to stub image embeddings), ``mamba`` (Mamba2),
``rwkv`` (RWKV6 time-mix + channel-mix).  Attention-bearing kinds are
followed by a dense or MoE FFN; ``mamba`` is FFN-free (Zamba-2 style);
``rwkv`` uses its own channel-mix.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.qtensor import QTensor
from repro.distributed.context import constrain, constrain_tree, scan_unroll

from . import layers, ssm
from .layers import (AttnSpec, MLPSpec, MoESpec, attn_apply, attn_decode,
                     attn_decode_paged, attn_init, dense_init, matmul,
                     mlp_apply, mlp_init, moe_apply, moe_init, rms_norm)
from .ssm import (Mamba2Spec, RWKV6Spec, mamba2_apply, mamba2_decode,
                  mamba2_init, mamba2_init_state, rwkv6_channel_mix,
                  rwkv6_channel_mix_init, rwkv6_init_state, rwkv6_time_mix,
                  rwkv6_time_mix_decode, rwkv6_time_mix_init)

Array = jnp.ndarray

ATTN_KINDS = ("attn", "local", "xattn")


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: Tuple[str, ...] = ("attn",)
    head_dim: Optional[int] = None
    mlp_kind: str = "swiglu"
    ffn: str = "dense"                    # dense | moe
    rope_theta: float = 10000.0
    rope_theta_local: Optional[float] = None   # gemma3 local layers
    window: Optional[int] = None
    softcap_attn: Optional[float] = None
    softcap_final: Optional[float] = None
    qk_norm: bool = False
    use_post_norm: bool = False           # gemma2/3 sandwich norms
    emb_scale: bool = False               # multiply embeddings by sqrt(d)
    tie_embeddings: bool = True
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    shared_attn_every: int = 0            # zamba2: shared block per scan group
    # RWKV
    rwkv_head_dim: int = 64
    rwkv_chunk: int = 16
    # Vision / audio stubs
    n_image_tokens: int = 0
    d_vision: int = 0
    n_codebooks: int = 1
    # Activation quantization (beyond-paper: the paper's §5 future-work
    # direction).  When set (e.g. "int8"), block inputs are fake-quantized
    # with per-tensor dynamic absmax + STE — simulating a W*A* deployment.
    act_fmt: Optional[str] = None
    # misc
    max_seq: int = 8192
    remat: bool = True
    sub_quadratic: bool = False           # eligible for long_500k
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % len(self.pattern) != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} not divisible by "
                f"unit length {len(self.pattern)}")

    @property
    def n_repeats(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def attn_spec(self, kind: str) -> AttnSpec:
        local = kind == "local"
        theta = (self.rope_theta_local if (local and self.rope_theta_local)
                 else self.rope_theta)
        return AttnSpec(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.hd,
            rope_theta=theta, window=self.window if local else None,
            softcap=self.softcap_attn, qk_norm=self.qk_norm,
            is_cross=(kind == "xattn"))

    def mlp_spec(self) -> MLPSpec:
        return MLPSpec(d_model=self.d_model, d_ff=self.d_ff, kind=self.mlp_kind)

    def moe_spec(self) -> MoESpec:
        return MoESpec(d_model=self.d_model, d_ff=self.d_ff,
                       n_experts=self.n_experts, top_k=self.top_k,
                       kind=self.mlp_kind, capacity_factor=self.capacity_factor)

    def mamba_spec(self) -> Mamba2Spec:
        return Mamba2Spec(d_model=self.d_model, d_state=self.ssm_state,
                          head_dim=self.ssm_head_dim, chunk=self.ssm_chunk)

    def rwkv_spec(self) -> RWKV6Spec:
        return RWKV6Spec(d_model=self.d_model, head_dim=self.rwkv_head_dim,
                         chunk=self.rwkv_chunk)


# ==========================================================================
# Parameter init
# ==========================================================================

def _block_init(key, cfg: LMConfig, kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"pre_norm_scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if kind in ATTN_KINDS:
        p["attn"] = attn_init(ks[0], cfg.attn_spec(kind))
        if kind == "xattn":
            p["xattn_gate"] = jnp.zeros((), jnp.float32)
        p["ffn_norm_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
        if cfg.ffn == "moe":
            p["moe"] = moe_init(ks[1], cfg.moe_spec())
            if cfg.n_shared_experts:
                shared_spec = MLPSpec(cfg.d_model,
                                      cfg.d_ff * cfg.n_shared_experts,
                                      cfg.mlp_kind)
                p["shared_mlp"] = mlp_init(ks[2], shared_spec)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.mlp_spec())
        if cfg.use_post_norm:
            p["post_norm_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
            p["ffn_post_norm_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
    elif kind == "mamba":
        p["mamba"] = mamba2_init(ks[0], cfg.mamba_spec())
    elif kind == "rwkv":
        p["tm"] = rwkv6_time_mix_init(ks[0], cfg.rwkv_spec())
        p["cm_norm_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["cm"] = rwkv6_channel_mix_init(ks[1], cfg.d_model, cfg.d_ff)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def lm_init(key, cfg: LMConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    if cfg.n_codebooks > 1:
        params["embed"] = (jax.random.normal(
            ks[0], (cfg.n_codebooks, cfg.vocab, cfg.d_model), jnp.float32) * 0.02)
    else:
        params["embed"] = (jax.random.normal(
            ks[0], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02)

    # stacked unit params: vmap init over repeats
    unit_keys = jax.random.split(ks[1], cfg.n_repeats)

    def init_unit(k):
        kk = jax.random.split(k, len(cfg.pattern))
        return {f"b{i}_{kind}": _block_init(kk[i], cfg, kind)
                for i, kind in enumerate(cfg.pattern)}

    params["stage"] = jax.vmap(init_unit)(unit_keys)

    if cfg.shared_attn_every:
        shared = {"pre_norm_scale": jnp.ones((cfg.d_model,), jnp.float32),
                  "attn": attn_init(ks[2], cfg.attn_spec("attn")),
                  "ffn_norm_scale": jnp.ones((cfg.d_model,), jnp.float32),
                  "mlp": mlp_init(ks[3], cfg.mlp_spec())}
        params["shared"] = shared

    if cfg.n_image_tokens:
        params["vision_proj"] = dense_init(ks[4], cfg.d_vision, cfg.d_model)

    params["final_norm_scale"] = jnp.ones((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            params["lm_head"] = (jax.random.normal(
                ks[5], (cfg.n_codebooks, cfg.d_model, cfg.vocab), jnp.float32)
                / np.sqrt(cfg.d_model))
        else:
            params["lm_head"] = dense_init(ks[5], cfg.d_model, cfg.vocab)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ==========================================================================
# Block application (full sequence)
# ==========================================================================

def _act_q(cfg: LMConfig, h: Array) -> Array:
    if cfg.act_fmt is None:
        return h
    from repro.core import get_format
    from repro.core.ste import fake_quant_rtn
    return fake_quant_rtn(h, get_format(cfg.act_fmt), -1)


def _apply_block(p, cfg: LMConfig, kind: str, x: Array, positions: Array,
                 ctx: Optional[Array], attn_chunk: Optional[int]):
    aux = {}
    if kind in ATTN_KINDS:
        with jax.named_scope(spans.ATTN):
            h = _act_q(cfg, rms_norm(x, p["pre_norm_scale"]))
            h = attn_apply(p["attn"], cfg.attn_spec(kind), h, positions,
                           ctx=ctx if kind == "xattn" else None,
                           chunk=attn_chunk)
            if kind == "xattn":
                h = jnp.tanh(p["xattn_gate"]).astype(x.dtype) * h
            if cfg.use_post_norm:
                h = rms_norm(h, p["post_norm_scale"])
            x = x + h
        with jax.named_scope(spans.MLP):
            h = _act_q(cfg, rms_norm(x, p["ffn_norm_scale"]))
            if cfg.ffn == "moe":
                h_moe, aux = moe_apply(p["moe"], cfg.moe_spec(), h)
                if cfg.n_shared_experts:
                    shared_spec = MLPSpec(cfg.d_model,
                                          cfg.d_ff * cfg.n_shared_experts,
                                          cfg.mlp_kind)
                    h_moe = h_moe + mlp_apply(p["shared_mlp"], shared_spec, h)
                h = h_moe
            else:
                h = mlp_apply(p["mlp"], cfg.mlp_spec(), h)
            if cfg.use_post_norm:
                h = rms_norm(h, p["ffn_post_norm_scale"])
            x = x + h
    elif kind == "mamba":
        h = rms_norm(x, p["pre_norm_scale"])
        x = x + mamba2_apply(p["mamba"], cfg.mamba_spec(), h)
    elif kind == "rwkv":
        h = rms_norm(x, p["pre_norm_scale"])
        x = x + rwkv6_time_mix(p["tm"], cfg.rwkv_spec(), h)
        h = rms_norm(x, p["cm_norm_scale"])
        x = x + rwkv6_channel_mix(p["cm"], h)
    return x, aux


def _apply_shared(p, cfg: LMConfig, x: Array, positions: Array,
                  attn_chunk: Optional[int]):
    h = rms_norm(x, p["pre_norm_scale"])
    h = attn_apply(p["attn"], cfg.attn_spec("attn"), h, positions,
                   chunk=attn_chunk)
    x = x + h
    h = rms_norm(x, p["ffn_norm_scale"])
    return x + mlp_apply(p["mlp"], cfg.mlp_spec(), h)


def _embed(params, cfg: LMConfig, tokens: Array) -> Array:
    with jax.named_scope(spans.EMBED):
        if cfg.n_codebooks > 1:
            # tokens: (b, l, n_codebooks)
            parts = [jnp.take(params["embed"][c], tokens[..., c], axis=0)
                     for c in range(cfg.n_codebooks)]
            x = sum(parts)
        elif isinstance(params["embed"], QTensor):
            # gather + per-row dequant: reads only the touched code rows
            x = params["embed"].take(tokens)
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
        x = x.astype(cfg.dtype)
        if cfg.emb_scale:
            x = x * np.sqrt(cfg.d_model).astype(np.float32)
        return x


def _head(params, cfg: LMConfig, x: Array) -> Array:
    with jax.named_scope(spans.HEAD):
        x = rms_norm(x, params["final_norm_scale"])
        # gather the (small, model-sharded) d dim of the activations before the
        # vocab matmul: keeps the contraction sharding aligned with the head
        # weights, avoiding per-chunk multi-GB logits all-reduces (§Perf log).
        x = constrain(x, "head_in")
        if cfg.n_codebooks > 1:
            if cfg.tie_embeddings:
                logits = jnp.einsum("bld,cvd->blcv", x,
                                    params["embed"].astype(x.dtype))
            else:
                logits = jnp.einsum("bld,cdv->blcv", x,
                                    params["lm_head"].astype(x.dtype))
        else:
            w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
            if isinstance(w, QTensor):
                # QTensor storage is out-major (vocab, d) for BOTH the tied
                # table and the (transposed-at-pack-time) untied head — the
                # transpose is baked into the layout, one kernel serves both
                logits = matmul(x, w)
            else:
                logits = x @ (w.T if cfg.tie_embeddings else w).astype(x.dtype)
        logits = constrain(logits.astype(jnp.float32), "logits")
        if cfg.softcap_final is not None:
            logits = cfg.softcap_final * jnp.tanh(logits / cfg.softcap_final)
        return logits


def _trunk(params, cfg: LMConfig, tokens: Array,
           image_embeds: Optional[Array] = None,
           attn_chunk: Optional[int] = None) -> Array:
    """Embedding + all blocks; returns final hidden states (b, l, d)."""
    x = _embed(params, cfg, tokens)
    l = tokens.shape[1]
    positions = jnp.arange(l)
    ctx = None
    if cfg.n_image_tokens and image_embeds is not None:
        ctx = matmul(image_embeds.astype(cfg.dtype), params["vision_proj"])

    def unit_body(x, unit_p):
        x = constrain(x, "residual")
        unit_p = constrain_tree(unit_p, "stage_params")
        for i, kind in enumerate(cfg.pattern):
            x, _ = _apply_block(unit_p[f"b{i}_{kind}"], cfg, kind, x,
                                positions, ctx, attn_chunk)
        if cfg.shared_attn_every:
            x = _apply_shared(params["shared"], cfg, x, positions, attn_chunk)
        return x, None

    body = unit_body
    if cfg.remat:
        body = jax.checkpoint(unit_body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["stage"],
                        unroll=scan_unroll(cfg.n_repeats))
    return x


def lm_forward(params, cfg: LMConfig, tokens: Array,
               image_embeds: Optional[Array] = None,
               attn_chunk: Optional[int] = None) -> Array:
    """Training forward: logits (b, l, [codebooks,] vocab) in fp32."""
    x = _trunk(params, cfg, tokens, image_embeds, attn_chunk)
    return _head(params, cfg, x)


def lm_loss(params, cfg: LMConfig, tokens: Array, labels: Array,
            image_embeds: Optional[Array] = None,
            attn_chunk: Optional[int] = None,
            logit_chunk: Optional[int] = None,
            per_example: bool = False):
    """Mean next-token CE with an optional *chunked head*: the full
    (b, l, vocab) logits tensor is never materialized — head + CE run as a
    rematerialized scan over sequence chunks, holding one
    (b, logit_chunk, vocab) slice at a time.  Essential at 256k-vocab,
    1M-token steps (see EXPERIMENTS.md §Perf).

    ``per_example=True`` additionally returns the (b,)-vector of
    per-example mean CE — the raw material for the cross-shard non-finite
    gate (DESIGN.md §12) — as ``(ce, ce_ex)``.  The scalar ``ce`` is
    computed from the identical elementwise terms either way, so the
    side output never perturbs the loss bits."""
    # deferred: no import cycle
    from repro.train.loop import _ce_terms, cross_entropy

    x = _trunk(params, cfg, tokens, image_embeds, attn_chunk)
    l = tokens.shape[1]
    if logit_chunk is None or logit_chunk >= l:
        if not per_example:
            return cross_entropy(_head(params, cfg, x), labels)
        terms = _ce_terms(_head(params, cfg, x), labels)
        return (jnp.mean(terms),
                jnp.mean(terms, axis=tuple(range(1, terms.ndim))))

    n_chunks = l // logit_chunk
    xc = x.reshape((x.shape[0], n_chunks, logit_chunk, x.shape[-1]))
    lc = labels.reshape((labels.shape[0], n_chunks, logit_chunk)
                        + labels.shape[2:])
    inputs = (xc.transpose(1, 0, 2, 3), jnp.moveaxis(lc, 1, 0))

    if not per_example:
        def chunk_ce(carry, inp):
            xch, lch = inp
            return carry + cross_entropy(_head(params, cfg, xch), lch), None

        body = jax.checkpoint(chunk_ce, prevent_cse=False)
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), inputs,
                                unroll=scan_unroll(n_chunks))
        return total / n_chunks

    def chunk_ce_ex(carry, inp):
        xch, lch = inp
        terms = _ce_terms(_head(params, cfg, xch), lch)
        tot, pex = carry
        return (tot + jnp.mean(terms),
                pex + jnp.mean(terms, axis=tuple(range(1, terms.ndim)))
                ), None

    body = jax.checkpoint(chunk_ce_ex, prevent_cse=False)
    (total, pex), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32),
         jnp.zeros((tokens.shape[0],), jnp.float32)),
        inputs, unroll=scan_unroll(n_chunks))
    return total / n_chunks, pex / n_chunks


# ==========================================================================
# Decode cache
# ==========================================================================

def _kv_zeros(shape, dtype, kv_quant):
    bits = layers.kv_bits(kv_quant)
    if bits:
        cshape = shape[:-1] + (shape[-1] // 2,) if bits == 4 else shape
        cdtype = jnp.uint8 if bits == 4 else jnp.int8
        return {"codes": jnp.zeros(cshape, cdtype),
                "scale": jnp.ones(shape[:-1] + (1,), jnp.float32)}
    return jnp.zeros(shape, dtype)


def init_cache(cfg: LMConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, kv_quant=False) -> Dict[str, Any]:
    """Cache pytree, stacked over repeats for scan-compatibility.

    ``cache_len`` is the max sequence length for global layers; local
    layers use a ring buffer of size ``window``.  ``kv_quant`` stores
    self-attention KV as quantized codes + per-vector fp32 absmax scales
    (the paper's quantizer applied to the serving cache): ``"int8"`` (or
    ``True``) halves decode cache HBM traffic, ``"int4"`` (nibbles packed
    two-per-byte along head_dim) quarters it — the pairing for int4
    weights.  Cross-attn KV stays in ``dtype``.
    """
    r = cfg.n_repeats
    unit: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.pattern):
        name = f"b{i}_{kind}"
        if kind == "attn":
            shape = (r, batch, cache_len, cfg.n_kv_heads, cfg.hd)
            unit[name] = {"k": _kv_zeros(shape, dtype, kv_quant),
                          "v": _kv_zeros(shape, dtype, kv_quant)}
        elif kind == "local":
            wl = min(cfg.window or cache_len, cache_len)
            shape = (r, batch, wl, cfg.n_kv_heads, cfg.hd)
            unit[name] = {"k": _kv_zeros(shape, dtype, kv_quant),
                          "v": _kv_zeros(shape, dtype, kv_quant)}
        elif kind == "xattn":
            shape = (r, batch, max(cfg.n_image_tokens, 1), cfg.n_kv_heads, cfg.hd)
            unit[name] = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        elif kind == "mamba":
            st = mamba2_init_state(cfg.mamba_spec(), batch, dtype)
            unit[name] = jax.tree.map(
                lambda a: jnp.zeros((r,) + a.shape, a.dtype), st)
        elif kind == "rwkv":
            st = rwkv6_init_state(cfg.rwkv_spec(), batch)
            unit[name] = jax.tree.map(
                lambda a: jnp.zeros((r,) + a.shape, a.dtype), st)
    cache = {"unit": unit}
    if cfg.shared_attn_every:
        shape = (r, batch, cache_len, cfg.n_kv_heads, cfg.hd)
        cache["shared"] = {"k": _kv_zeros(shape, dtype, kv_quant),
                          "v": _kv_zeros(shape, dtype, kv_quant)}
    return cache


def init_paged_pool(cfg: LMConfig, n_blocks: int, block_size: int,
                    dtype=jnp.bfloat16, kv_quant=False) -> Dict[str, Any]:
    """Paged KV pool (DESIGN.md §13): ``n_blocks`` blocks of
    ``block_size`` tokens shared by every slot's block table, stacked over
    repeats like :func:`init_cache`.  Attention-family patterns only (the
    paged scheduler needs every ring to cover ``cache_len``).

    A quantized pool is stored in the decode kernel's layout, so the tick
    reads and writes it where it lies: codes ``(r, n_blocks, block_size,
    kvh*hd_c)`` (lane-dense rows of every kv head) and fp32 scales
    ``(r, n_blocks, kvh, block_size)`` (lane-dense along the block's
    tokens).  A dense pool is ``(r, n_blocks, block_size, kvh, hd)``.
    """
    bad = [k for k in cfg.pattern if k not in ("attn", "local")]
    if bad:
        raise ValueError(f"a paged pool needs attn/local-only patterns, "
                         f"got {bad}")
    r, g, hd = cfg.n_repeats, cfg.n_kv_heads, cfg.hd
    bits = layers.kv_bits(kv_quant)

    def kv():
        if not bits:
            return jnp.zeros((r, n_blocks, block_size, g, hd), dtype)
        hd_c = hd // 2 if bits == 4 else hd
        return {"codes": jnp.zeros((r, n_blocks, block_size, g * hd_c),
                                   jnp.uint8 if bits == 4 else jnp.int8),
                "scale": jnp.ones((r, n_blocks, g, block_size),
                                  jnp.float32)}

    pool: Dict[str, Any] = {"unit": {f"b{i}_{kind}": {"k": kv(), "v": kv()}
                                     for i, kind in enumerate(cfg.pattern)}}
    if cfg.shared_attn_every:
        pool["shared"] = {"k": kv(), "v": kv()}
    return pool


def cache_insert(pool_cache, row_cache, slot):
    """Insert a single-request cache (batch=1, same ``cache_len``) into a
    slot-pool cache at batch index ``slot``.

    Every cache leaf — KV rings, quantized code/scale pairs, mamba/rwkv
    recurrent states — is stacked ``(repeats, batch, ...)``, so one
    ``dynamic_update_index_in_dim`` on axis 1 covers the whole pytree.
    The slot's ENTIRE row is replaced, which is what makes slot reuse
    leak-free: no KV from the slot's previous occupant survives the
    insert (and the ring-validity rule masks the not-yet-written tail
    until decode overwrites it).
    """
    def one(pool, row):
        return jax.lax.dynamic_update_index_in_dim(
            pool, jax.lax.squeeze(row, (1,)).astype(pool.dtype), slot, 1)

    with jax.named_scope(spans.KV_CACHE):
        return jax.tree.map(one, pool_cache, row_cache)


def cache_insert_paged(pool_cache, row_cache, table, write_mask):
    """Scatter a single-request cache row into PAGED pool blocks.

    ``pool_cache`` is an :func:`init_paged_pool` pool; ``row_cache`` the
    batch=1 full-``cache_len`` row (``(r, 1, bps*bs, ...)``, same
    kv_quant format), re-laid out here into the pool's block layout (a
    one-request-sized op).  ``table`` (bps,) int32 gives the destination
    block per chunk; chunks with ``write_mask`` False (prefix blocks
    SHARED from the trie, whose bytes are already in the pool) are
    redirected into the reserved dump block 0 so a consumer never
    rewrites a shared block.  Written chunks land byte-identical to what
    :func:`cache_insert` puts in a dense ring row, because chunk i of the
    row IS ring slots ``[i*bs, (i+1)*bs)``.
    """
    bps = table.shape[0]

    def blocks(row):                                 # (r, bps, bs, ...)
        row = jax.lax.squeeze(row, (1,))
        return row.reshape((row.shape[0], bps, -1) + row.shape[2:])

    def one(pool, row):
        if layers._is_quantized_cache(pool):
            codes = blocks(row["codes"])             # (r, bps, bs, g, hd_c)
            scale = blocks(row["scale"])[..., 0]     # (r, bps, bs, g)
            row = {"codes": codes.reshape(codes.shape[:3] + (-1,)),
                   "scale": jnp.swapaxes(scale, 2, 3)}
        else:
            row = blocks(row)
        return jax.tree.map(
            lambda p, u: p.at[:, bids].set(u.astype(p.dtype)), pool, row)

    with jax.named_scope(spans.KV_CACHE):
        bids = jnp.where(write_mask, table, 0).astype(jnp.int32)
        return jax.tree.map(one, pool_cache, row_cache,
                            is_leaf=layers._is_quantized_cache)


# ==========================================================================
# Prefill (fills cache) and decode (one token)
# ==========================================================================

def _kv_to_cache(k, v, kind: str, cfg: LMConfig, cache_len: int,
                 kv_quant=False, pads: Optional[Array] = None):
    """Pack full-sequence (k, v) into the decode-cache layout.

    ``pads`` (b,) — per-row left-pad widths under ragged prompts: row i's
    column c holds position ``c - pads[i]`` and must land at ring slot
    ``pos % ring_len`` (the slot the decode validity rule will look up),
    so each row is scatter-written at its own offsets; pad columns
    (negative positions) and positions older than the ring are dumped
    into a scratch slot and sliced off.  ``pads=None`` keeps the legacy
    position==column layout (training / un-padded prefill) unchanged.
    """
    b, l = k.shape[0], k.shape[1]
    bits = layers.kv_bits(kv_quant)

    def store(x):
        return layers.kv_quantize(x, bits) if bits else x.astype(cfg.dtype)

    if kind == "xattn":
        return {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}

    if pads is not None:
        ring_len = (min(cfg.window or cache_len, cache_len)
                    if kind == "local" else cache_len)
        positions = jnp.arange(l)[None, :] - pads[:, None]       # (b, l)
        length = l - pads                                        # (b,)
        keep = (positions >= 0) & (positions >= length[:, None] - ring_len)
        slots = jnp.where(keep, positions % ring_len, ring_len)  # dump row
        bidx = jnp.arange(b)[:, None]

        def scatter(t):
            def one(vals, fill):
                buf = jnp.full((b, ring_len + 1) + vals.shape[2:], fill,
                               vals.dtype)
                return buf.at[bidx, slots].set(vals)[:, :ring_len]

            s = store(t)
            if bits:
                return {"codes": one(s["codes"], 0),
                        "scale": one(s["scale"], 1.0)}
            return one(s, 0)

        return {"k": scatter(k), "v": scatter(v)}

    if kind == "local":
        wl = min(cfg.window or cache_len, cache_len)
        take = min(wl, l)
        slots = jnp.arange(l - take, l) % wl

        def ring(t):
            vals = store(t[:, l - take:])
            if bits:
                return {
                    "codes": jnp.zeros((b, wl) + vals["codes"].shape[2:],
                                       vals["codes"].dtype)
                    .at[:, slots].set(vals["codes"]),
                    "scale": jnp.ones((b, wl) + t.shape[2:-1] + (1,),
                                      jnp.float32)
                    .at[:, slots].set(vals["scale"]),
                }
            return (jnp.zeros((b, wl) + t.shape[2:], cfg.dtype)
                    .at[:, slots].set(vals))

        return {"k": ring(k), "v": ring(v)}
    pad = cache_len - l

    def pad_store(t):
        s = store(t)
        return jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                              constant_values=1.0 if a.dtype == jnp.float32
                              and bits else 0),
            s)

    return {"k": pad_store(k), "v": pad_store(v)}


def lm_prefill(params, cfg: LMConfig, tokens: Array,
               image_embeds: Optional[Array] = None,
               attn_chunk: Optional[int] = None,
               cache_len: Optional[int] = None,
               kv_quant=False,
               prompt_lens: Optional[Array] = None):
    """Forward + cache fill in one pass.  Returns (last logits, cache).

    ``prompt_lens`` (b,) — real (un-padded) prompt length per row for
    left-padded ragged batches.  Rows get per-row positions
    ``col - pad`` (pads negative), pad keys are masked out of every
    attention score, and the KV cache is written at position-indexed ring
    slots — so generations are *pad-invariant*: identical to running each
    prompt alone (the property continuous batching's per-slot
    prefill-insert relies on), and prompt widths become bucketable.
    Attention-family blocks only; recurrent (mamba/rwkv) blocks still
    consume pad tokens, so callers gate ``prompt_lens`` on attention-only
    patterns.
    """
    b, l = tokens.shape[0], tokens.shape[1]
    cache_len = cache_len or l
    x = _embed(params, cfg, tokens)
    pads = None
    if prompt_lens is None:
        positions = jnp.arange(l)
    else:
        pads = (l - prompt_lens).astype(jnp.int32)               # (b,)
        positions = jnp.arange(l)[None, :] - pads[:, None]       # (b, l)
    ctx = None
    if cfg.n_image_tokens and image_embeds is not None:
        ctx = matmul(image_embeds.astype(cfg.dtype), params["vision_proj"])

    def unit_body(x, unit_p):
        x = constrain(x, "residual")
        unit_p = constrain_tree(unit_p, "stage_params")
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            name = f"b{i}_{kind}"
            p = unit_p[name]
            if kind in ATTN_KINDS:
                with jax.named_scope(spans.ATTN):
                    h = rms_norm(x, p["pre_norm_scale"])
                    o, (k, v) = attn_apply(
                        p["attn"], cfg.attn_spec(kind), h, positions,
                        ctx=ctx if kind == "xattn" else None,
                        chunk=attn_chunk, return_kv=True)
                    with jax.named_scope(spans.KV_CACHE):
                        new_caches[name] = _kv_to_cache(
                            k, v, kind, cfg, cache_len, kv_quant, pads=pads)
                    if kind == "xattn":
                        o = jnp.tanh(p["xattn_gate"]).astype(x.dtype) * o
                    if cfg.use_post_norm:
                        o = rms_norm(o, p["post_norm_scale"])
                    x = x + o
                with jax.named_scope(spans.MLP):
                    h = rms_norm(x, p["ffn_norm_scale"])
                    if cfg.ffn == "moe":
                        hm, _ = moe_apply(p["moe"], cfg.moe_spec(), h)
                        if cfg.n_shared_experts:
                            shared_spec = MLPSpec(cfg.d_model,
                                                  cfg.d_ff * cfg.n_shared_experts,
                                                  cfg.mlp_kind)
                            hm = hm + mlp_apply(p["shared_mlp"], shared_spec, h)
                        h = hm
                    else:
                        h = mlp_apply(p["mlp"], cfg.mlp_spec(), h)
                    if cfg.use_post_norm:
                        h = rms_norm(h, p["ffn_post_norm_scale"])
                    x = x + h
            elif kind == "mamba":
                h = rms_norm(x, p["pre_norm_scale"])
                o, st = mamba2_apply(p["mamba"], cfg.mamba_spec(), h,
                                     return_state=True)
                new_caches[name] = st
                x = x + o
            elif kind == "rwkv":
                h = rms_norm(x, p["pre_norm_scale"])
                o, st = rwkv6_time_mix(p["tm"], cfg.rwkv_spec(), h,
                                       return_state=True)
                x = x + o
                h2 = rms_norm(x, p["cm_norm_scale"])
                st["shift_cm"] = h2[:, -1].astype(jnp.float32)
                new_caches[name] = st
                x = x + rwkv6_channel_mix(p["cm"], h2)
        if cfg.shared_attn_every:
            hs = rms_norm(x, params["shared"]["pre_norm_scale"])
            o, (k, v) = attn_apply(params["shared"]["attn"],
                                   cfg.attn_spec("attn"), hs, positions,
                                   chunk=attn_chunk, return_kv=True)
            new_caches["__shared__"] = _kv_to_cache(k, v, "attn", cfg,
                                                    cache_len, kv_quant,
                                                    pads=pads)
            x = x + o
            h = rms_norm(x, params["shared"]["ffn_norm_scale"])
            x = x + mlp_apply(params["shared"]["mlp"], cfg.mlp_spec(), h)
        return x, new_caches

    body = unit_body
    if cfg.remat:
        body = jax.checkpoint(unit_body, prevent_cse=False)
    x, stacked = jax.lax.scan(body, x, params["stage"],
                              unroll=scan_unroll(cfg.n_repeats))
    shared_cache = stacked.pop("__shared__", None)
    cache = {"unit": stacked}
    if shared_cache is not None:
        cache["shared"] = shared_cache
    logits = _head(params, cfg, x[:, -1:])
    return logits, cache


def quantize_cache(cfg: LMConfig, cache, kv_quant):
    """Convert a DENSE cache (``init_cache(..., kv_quant=False)`` layout)
    to the quantized layout ``init_cache(..., kv_quant=...)`` builds.

    Chunked prefill accumulates its partial cache densely (so chunk
    attention reads earlier chunks at exactly the precision monolithic
    prefill reads its in-flight K/V — the token-parity argument) and the
    quantization happens once here, at slot-insert time.  Per-vector
    absmax over the scattered values is bitwise the same as quantizing
    before the scatter, and all-zero (unwritten) slots produce
    codes=0/scale=1 — the ``init_cache`` fill — so the layout matches a
    monolithic ``lm_prefill(kv_quant=...)`` cache exactly (values agree
    to fp summation-order tolerance, same as the dense chunked path).
    """
    bits = layers.kv_bits(kv_quant)
    if not bits:
        return cache

    def q(leaf):
        with jax.named_scope(spans.KV_CACHE):
            return {"k": layers.kv_quantize(leaf["k"], bits),
                    "v": layers.kv_quantize(leaf["v"], bits)}

    unit = dict(cache["unit"])
    for i, kind in enumerate(cfg.pattern):
        name = f"b{i}_{kind}"
        if kind in ("attn", "local"):
            unit[name] = q(unit[name])
    out = {"unit": unit}
    if cfg.shared_attn_every:
        out["shared"] = q(cache["shared"])
    return out


def lm_prefill_chunk(params, cfg: LMConfig, cache, tokens: Array,
                     start_pos: Array, chunk_lens: Optional[Array] = None):
    """Advance a partial prefill by ONE chunk of prompt tokens.

    tokens: (b, cw) — the next chunk per row, right-padded to the fixed
    chunk width.  start_pos: (b,) absolute position of column 0 (i.e.
    tokens already in the cache per row).  chunk_lens: (b,) real token
    count this chunk (None -> full width).  ``cache`` must be a DENSE
    partial cache holding every position < start_pos; the chunk's K/V
    are ring-scattered into it (see ``quantize_cache`` for the deferred
    kv-quant step).

    Attention-family blocks only: recurrent (mamba/rwkv) blocks would
    need their state threaded per-chunk — callers gate on
    ``serve.engine.attn_only`` (which also excludes capacity-based MoE,
    whose per-group routing makes chunked != monolithic).  Returns
    (logits of each row's LAST REAL token (b, 1, [codebooks,] vocab),
    new_cache).
    """
    b, cw = tokens.shape[0], tokens.shape[1]
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), cw, jnp.int32)
    positions = start_pos[:, None] + jnp.arange(cw)[None, :]       # (b, cw)
    x = _embed(params, cfg, tokens)

    def unit_body(x, scanned):
        unit_p, unit_c = scanned
        new_c = {}
        for i, kind in enumerate(cfg.pattern):
            name = f"b{i}_{kind}"
            p = unit_p[name]
            if kind in ATTN_KINDS:
                with jax.named_scope(spans.ATTN):
                    h = rms_norm(x, p["pre_norm_scale"])
                    o, ck, cv = layers.attn_chunk_apply(
                        p["attn"], cfg.attn_spec(kind), h, positions,
                        chunk_lens, unit_c[name]["k"], unit_c[name]["v"])
                    if kind == "xattn":
                        o = jnp.tanh(p["xattn_gate"]).astype(x.dtype) * o
                    new_c[name] = {"k": ck, "v": cv}
                    if cfg.use_post_norm:
                        o = rms_norm(o, p["post_norm_scale"])
                    x = x + o
                with jax.named_scope(spans.MLP):
                    h = rms_norm(x, p["ffn_norm_scale"])
                    if cfg.ffn == "moe":
                        hm, _ = moe_apply(p["moe"], cfg.moe_spec(), h)
                        if cfg.n_shared_experts:
                            shared_spec = MLPSpec(cfg.d_model,
                                                  cfg.d_ff * cfg.n_shared_experts,
                                                  cfg.mlp_kind)
                            hm = hm + mlp_apply(p["shared_mlp"], shared_spec, h)
                        h = hm
                    else:
                        h = mlp_apply(p["mlp"], cfg.mlp_spec(), h)
                    if cfg.use_post_norm:
                        h = rms_norm(h, p["ffn_post_norm_scale"])
                    x = x + h
            else:
                raise NotImplementedError(
                    f"chunked prefill needs attention-family blocks; "
                    f"{cfg.name} has {kind!r} (recurrent state is not "
                    f"threaded across chunks — use monolithic prefill)")
        if cfg.shared_attn_every:
            hs = rms_norm(x, params["shared"]["pre_norm_scale"])
            o, ck, cv = layers.attn_chunk_apply(
                params["shared"]["attn"], cfg.attn_spec("attn"), hs,
                positions, chunk_lens, unit_c["__shared__"]["k"],
                unit_c["__shared__"]["v"])
            new_c["__shared__"] = {"k": ck, "v": cv}
            x = x + o
            h = rms_norm(x, params["shared"]["ffn_norm_scale"])
            x = x + mlp_apply(params["shared"]["mlp"], cfg.mlp_spec(), h)
        return x, new_c

    scanned_cache = dict(cache["unit"])
    if cfg.shared_attn_every:
        scanned_cache["__shared__"] = cache["shared"]

    # same carry-DUS dataflow as lm_decode: the cache is updated in place
    # per repeat instead of double-buffered as stacked scan ys
    def carry_body(carry, unit_p):
        x, full_cache, r = carry
        with jax.named_scope(spans.KV_CACHE):
            unit_c = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False),
                full_cache)
        x, new_c = unit_body(x, (unit_p, unit_c))
        with jax.named_scope(spans.KV_CACHE):
            full_cache = jax.tree.map(
                lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                    full, upd.astype(full.dtype), r, 0),
                full_cache, new_c)
        return (x, full_cache, r + 1), None

    (x, new_stacked, _), _ = jax.lax.scan(
        carry_body, (x, scanned_cache, jnp.int32(0)), params["stage"],
        unroll=scan_unroll(cfg.n_repeats))
    shared_cache = new_stacked.pop("__shared__", None)
    new_cache = {"unit": new_stacked}
    if shared_cache is not None:
        new_cache["shared"] = shared_cache
    # head over each row's last real column only (pad outputs are garbage)
    last = jnp.clip(chunk_lens - 1, 0)[:, None, None]
    x_last = jnp.take_along_axis(x, last, axis=1)                  # (b, 1, d)
    return _head(params, cfg, x_last), new_cache


def lm_decode(params, cfg: LMConfig, cache, tokens: Array, pos: Array,
              token_mask: Optional[Array] = None,
              block_tables: Optional[Array] = None):
    """One-token decode.  tokens: (b, 1[, codebooks]); pos: (b,) int32.

    ``token_mask`` (b,) bool — live rows under continuous batching (free /
    retired slots decode along but must not consume MoE expert capacity).
    With ``block_tables`` (b, bps) int32, ``cache`` is the PAGED pool
    (:func:`init_paged_pool`, shared by every row) and attention
    reads/writes route through the tables into each layer's blocks where
    they lie (DESIGN.md §13); paged mode requires an attention-only
    full-ring pattern.  Returns (logits (b, 1, ...), new_cache).
    """
    if block_tables is not None:
        bad = [k for k in cfg.pattern if k not in ("attn", "local")]
        if bad:
            raise ValueError(f"paged decode requires attn/local-only "
                             f"patterns, got {bad}")
    x = _embed(params, cfg, tokens)

    def unit_body(x, scanned, layer=None):
        # paged: ``unit_c`` is the whole stacked pool and ``layer`` the
        # repeat whose blocks attention reads and writes
        unit_p, unit_c = scanned
        new_c = {}
        for i, kind in enumerate(cfg.pattern):
            name = f"b{i}_{kind}"
            p = unit_p[name]
            if kind in ATTN_KINDS:
                with jax.named_scope(spans.ATTN):
                    h = rms_norm(x, p["pre_norm_scale"])
                    spec = cfg.attn_spec(kind)
                    cross_kv = None
                    if kind == "xattn":
                        cross_kv = (unit_c[name]["k"].astype(x.dtype),
                                    unit_c[name]["v"].astype(x.dtype))
                    if block_tables is not None:
                        o, ck, cv = attn_decode_paged(
                            p["attn"], spec, h, pos,
                            unit_c[name]["k"], unit_c[name]["v"],
                            block_tables, layer)
                    else:
                        o, ck, cv = attn_decode(p["attn"], spec, h, pos,
                                                unit_c[name]["k"],
                                                unit_c[name]["v"],
                                                cross_kv=cross_kv)
                    if kind == "xattn":
                        o = jnp.tanh(p["xattn_gate"]).astype(x.dtype) * o
                    new_c[name] = {"k": ck, "v": cv}
                    if cfg.use_post_norm:
                        o = rms_norm(o, p["post_norm_scale"])
                    x = x + o
                with jax.named_scope(spans.MLP):
                    h = rms_norm(x, p["ffn_norm_scale"])
                    if cfg.ffn == "moe":
                        hm, _ = moe_apply(p["moe"], cfg.moe_spec(), h,
                                          token_mask=token_mask)
                        if cfg.n_shared_experts:
                            shared_spec = MLPSpec(cfg.d_model,
                                                  cfg.d_ff * cfg.n_shared_experts,
                                                  cfg.mlp_kind)
                            hm = hm + mlp_apply(p["shared_mlp"], shared_spec, h)
                        h = hm
                    else:
                        h = mlp_apply(p["mlp"], cfg.mlp_spec(), h)
                    if cfg.use_post_norm:
                        h = rms_norm(h, p["ffn_post_norm_scale"])
                    x = x + h
            elif kind == "mamba":
                h = rms_norm(x, p["pre_norm_scale"])
                o, st = mamba2_decode(p["mamba"], cfg.mamba_spec(), h,
                                      unit_c[name])
                new_c[name] = st
                x = x + o
            elif kind == "rwkv":
                h = rms_norm(x, p["pre_norm_scale"])
                o, st = rwkv6_time_mix_decode(
                    p["tm"], cfg.rwkv_spec(), h,
                    {"shift_tm": unit_c[name]["shift_tm"],
                     "wkv": unit_c[name]["wkv"]})
                x = x + o
                h2 = rms_norm(x, p["cm_norm_scale"])
                xx = unit_c[name]["shift_cm"].astype(x.dtype)[:, None, :]
                x = x + ssm.rwkv6_channel_mix(p["cm"], h2, xx=xx)
                st["shift_cm"] = h2[:, 0].astype(jnp.float32)
                new_c[name] = st
        if cfg.shared_attn_every:
            hs = rms_norm(x, params["shared"]["pre_norm_scale"])
            if block_tables is not None:
                o, ck, cv = attn_decode_paged(
                    params["shared"]["attn"], cfg.attn_spec("attn"), hs,
                    pos, unit_c["__shared__"]["k"],
                    unit_c["__shared__"]["v"], block_tables, layer)
            else:
                o, ck, cv = attn_decode(params["shared"]["attn"],
                                        cfg.attn_spec("attn"), hs, pos,
                                        unit_c["__shared__"]["k"],
                                        unit_c["__shared__"]["v"])
            new_c["__shared__"] = {"k": ck, "v": cv}
            x = x + o
            h = rms_norm(x, params["shared"]["ffn_norm_scale"])
            x = x + mlp_apply(params["shared"]["mlp"], cfg.mlp_spec(), h)
        return x, new_c

    scanned_cache = dict(cache["unit"])
    if cfg.shared_attn_every:
        scanned_cache["__shared__"] = cache["shared"]

    # Carry the FULL stacked cache.  A scan emitting the new cache as
    # stacked ys would double-buffer the whole KV cache (xs + ys live
    # simultaneously).  The paged pool is read and written where it lies:
    # each layer writes its new token into the stacked leaves at
    # (r, block, offset) and attention reads layer r's blocks from them.
    # The dense ring (and lm_prefill_chunk's partial cache) still slice
    # repeat `r` out of the carry and dynamic-update-slice it back.
    def carry_body(carry, unit_p):
        x, full_cache, r = carry
        if block_tables is not None:
            x, full_cache = unit_body(x, (unit_p, full_cache), r)
            return (x, full_cache, r + 1), None
        with jax.named_scope(spans.KV_CACHE):
            unit_c = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False),
                full_cache)
        x, new_c = unit_body(x, (unit_p, unit_c))
        with jax.named_scope(spans.KV_CACHE):
            full_cache = jax.tree.map(
                lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                    full, upd.astype(full.dtype), r, 0),
                full_cache, new_c)
        return (x, full_cache, r + 1), None

    (x, new_stacked, _), _ = jax.lax.scan(
        carry_body, (x, scanned_cache, jnp.int32(0)), params["stage"],
        unroll=scan_unroll(cfg.n_repeats))
    shared_cache = new_stacked.pop("__shared__", None)
    new_cache = {"unit": new_stacked}
    if shared_cache is not None:
        new_cache["shared"] = shared_cache
    return _head(params, cfg, x), new_cache
