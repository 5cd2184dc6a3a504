"""Plain float32 reference of a dense GQA decoder (granite-3 family, as the
configuration file states it): RMSNorm, RoPE (half-split), grouped-query
causal attention, SwiGLU MLP, tied embedding head.

Straight ``jax.numpy`` in float32 with every matmul at HIGHEST precision;
no kernels, no cache, no batching beyond the rows it is given.  It imports
nothing of the program under test.  Departures from the published granite
config are the configuration file's ``reduced`` keys: the multipliers and
norm epsilon are taken from the file, as the program runs them.

Three uses:

* ``loss_and_grads``: training loss and gradients of a stacked tree.
* ``adamw_lotion_update``: one clipped AdamW step with the decoupled
  LOTION term (closed-form gradient of ``lam/2 * sum f (hi-w)(w-lo)``).
* ``served_gaps``: for a served request, the gap between the reference's
  best logit and its logit of each served token.  The weights are rebuilt
  layer by layer from the seed and quantized round-to-nearest int4 with
  one absmax scale per matrix; K/V of positions at or past the prompt end
  are read back through per-vector int4, as the configuration serves them.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
QBLOCK = 256           # query rows per attention block
FP8 = jnp.float8_e4m3fn


class Precision:
    """The operand precision of every matmul: float32 at HIGHEST (the
    reference), or each operand first rounded to float8 e4m3 (the control:
    the step below the configuration's bfloat16).  Read while tracing, so
    set it before a function is traced."""
    fp8 = False


def mm(eq, *xs):
    if Precision.fp8:
        xs = tuple(x.astype(FP8).astype(jnp.float32) for x in xs)
    return jnp.einsum(eq, *xs, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (..., S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def int4_vec(x):
    """Per-vector (last axis) absmax int4 round trip, codes in [-7, 7]."""
    a = jnp.max(jnp.abs(x), -1, keepdims=True)
    s = jnp.where(a > 0, a / 7.0, 1.0)
    return jnp.clip(jnp.rint(x / s), -7, 7) * s


def int4_matrix(w):
    """Round-to-nearest int4 with one absmax scale for the whole matrix."""
    a = jnp.max(jnp.abs(w))
    s = jnp.where(a > 0, a / 7.0, 1.0)
    return jnp.clip(jnp.rint(w / s), -7, 7) * s


def attention(q, k, v, scale, qpos0=0, kq=None, vq=None, plen=None):
    """Causal GQA attention, query blocks of ``QBLOCK``.  q (B, S, H, hd),
    k/v (B, S, G, hd).  With ``kq``/``vq``, queries at positions >= plen
    read those (the served cache) instead of k/v."""
    b, s, h, hd = q.shape
    g = k.shape[2]
    qg = q.reshape(b, s, g, h // g, hd)
    kpos = jnp.arange(s)
    nb = -(-s // QBLOCK)
    pad = nb * QBLOCK - s
    qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))

    def one(kk, vv, qb, qp):
        sc = mm("bqgrd,bkgd->bgrqk", qb, kk) * scale
        ok = kpos[None, :] <= qp[:, None]
        sc = jnp.where(ok, sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("bgrqk,bkgd->bqgrd", p, vv)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * QBLOCK, QBLOCK, 1)
        qp = i * QBLOCK + jnp.arange(QBLOCK)
        o = one(k, v, qb, qp)
        if kq is not None:
            oq = one(kq, vq, qb, qp)
            o = jnp.where((qp >= plen)[None, :, None, None, None], oq, o)
        return o

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nb * QBLOCK, h, hd)[:, :s]
    return out


def block_apply(x, p, c, pos, served=False, plen=None):
    m = W.dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    b, s, _ = x.shape
    hN = rms_norm(x, p["pre_norm_scale"], eps)
    q = mm("bsd,de->bse", hN, p["attn"]["wq"]).reshape(b, s, m["h"], m["hd"])
    k = mm("bsd,de->bse", hN, p["attn"]["wk"]).reshape(b, s, m["g"], m["hd"])
    v = mm("bsd,de->bse", hN, p["attn"]["wv"]).reshape(b, s, m["g"], m["hd"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    kw = {}
    if served:
        kw = dict(kq=int4_vec(k), vq=int4_vec(v), plen=plen)
    o = attention(q, k, v, c["attention_multiplier"], **kw)
    o = o.reshape(b, s, m["h"] * m["hd"])
    x = x + c["residual_multiplier"] * mm("bse,ed->bsd", o, p["attn"]["wo"])
    hN = rms_norm(x, p["ffn_norm_scale"], eps)
    up = mm("bsd,df->bsf", hN, p["mlp"]["w_up"])
    gate = mm("bsd,df->bsf", hN, p["mlp"]["w_gate"])
    y = mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp"]["w_down"])
    return x + c["residual_multiplier"] * y


def head_logits(x, embed, final_scale, c):
    x = rms_norm(x, final_scale, c["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, embed) / c["logits_scaling"]


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def loss(params, tokens, labels, c, logit_chunk: int = 512):
    """Mean next-token cross-entropy of a stacked tree."""
    s = tokens.shape[1]
    pos = jnp.arange(s)
    x = params["embed"][tokens] * c["embedding_multiplier"]

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: block_apply(x, lp, c, pos))(
            x, lp), None

    x, _ = jax.lax.scan(body, x, params["stage"]["b0_attn"])
    n = s // logit_chunk

    def ce(tot, i):
        xc = jax.lax.dynamic_slice_in_dim(x, i * logit_chunk, logit_chunk, 1)
        lc = jax.lax.dynamic_slice_in_dim(labels, i * logit_chunk,
                                          logit_chunk, 1)
        lg = head_logits(xc, params["embed"], params["final_norm_scale"], c)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(jax.checkpoint(ce), jnp.zeros((), jnp.float32),
                          jnp.arange(n))
    return tot / (tokens.shape[0] * s)


def loss_and_grads(params, batch, c, logit_chunk: int = 512):
    return jax.value_and_grad(loss)(params, batch["tokens"], batch["labels"],
                                    c, min(logit_chunk, batch["tokens"].shape[1]))


def eligible(path: str, x) -> bool:
    """The LOTION/int4 set of the configuration: matrices of at least
    ``min_size`` elements that are not the embedding or a norm."""
    return (x.ndim >= 2 and x[0].size >= 1024 and "embed" not in path
            and "norm" not in path)


def lotion_terms(w, nu, lam):
    """Penalty value ``lam/2 sum nu (hi-w)(w-lo)`` and its gradient, with
    one absmax scale per trailing matrix of a stacked leaf."""
    a = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)
    s = jnp.where(a > 0, a / 7.0, 1.0)
    z = jnp.clip(w / s, -7.0, 7.0)
    lo, hi = jnp.floor(z) * s, jnp.ceil(z) * s
    pen = 0.5 * lam * jnp.sum(nu * (hi - w) * (w - lo))
    grad = 0.5 * lam * nu * ((hi - w) - (w - lo))
    return pen, grad


def lr_at(count: int, t: Dict) -> float:
    """Linear warmup then cosine to a tenth (the configuration's
    schedule), at optimizer step ``count`` (1-based)."""
    lr, warm, total = t["lr"], t["warmup"], t["total_steps"]
    if count < warm:
        return lr * count / max(warm, 1)
    prog = min(max((count - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * prog)))


@functools.partial(jax.jit, static_argnums=(5,))
def _leaf_update(w, g, mu, nu, scalars, lam):
    lr, bc1, bc2, cs, b1, b2, eps, wd = (scalars[i] for i in range(8))
    gc = g * cs
    pen = jnp.zeros((), jnp.float32)
    if lam is not None:
        pen, pg = lotion_terms(w, nu, lam)
        gc = gc + pg
    mu2 = b1 * mu + (1 - b1) * gc
    nu2 = b2 * nu + (1 - b2) * gc * gc
    upd = (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + eps)
    return w - lr * (upd + wd * w), mu2, nu2, pen


def adamw_lotion_update(params, grads, host_mu, host_nu, count: int, t: Dict):
    """One step of the configuration's optimizer, leaf by leaf.  The
    moments live on the host between steps (numpy trees), so the device
    holds only the weights and one gradient tree.  Returns (new params,
    new host moments, LOTION penalty of the pre-update weights)."""
    gn = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))))
    cs = min(1.0, t["clip_norm"] / max(gn, 1e-9))
    bc1, bc2 = 1 - t["b1"] ** count, 1 - t["b2"] ** count
    sc = jnp.asarray([lr_at(count, t), bc1, bc2, cs, t["b1"], t["b2"],
                      t["eps"], t["weight_decay"]], jnp.float32)
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    gl, ml, nl = (jax.tree.leaves(x) for x in (grads, host_mu, host_nu))
    new_p, new_m, new_n, pen = [], [], [], 0.0
    for (path, w), g, m, n in zip(flat, gl, ml, nl):
        name = jax.tree_util.keystr(path)
        lam = t["lam"] if eligible(name, w) else None
        w2, m2, n2, pv = _leaf_update(w, g, jnp.asarray(m), jnp.asarray(n),
                                      sc, lam)
        new_p.append(w2)
        new_m.append(np.asarray(m2))
        new_n.append(np.asarray(n2))
        pen += float(pv)
    unf = jax.tree_util.tree_unflatten
    return unf(tdef, new_p), unf(tdef, new_m), unf(tdef, new_n), pen, gn


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """Norm of every leaf, a stacked leaf split into its layers."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if "stage" in name:
            out[name] = np.asarray(jnp.sqrt(jnp.sum(
                jnp.square(x.reshape(x.shape[0], -1)), axis=1)))
        else:
            out[name] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(x))))[None]
    return out


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def served_logits(seed32, c, tokens, plen):
    """Reference logits (S_pad, V) of one served sequence ``tokens``
    (1, S_pad: the prompt, then the served tokens, right-padded; causality
    keeps pads unseen).  Weights are rebuilt per layer from the seed and
    int4-quantized, as the configuration serves them.  ``c`` is a
    hashable view of the configuration (``frozen``)."""
    c = dict(c)
    ke, kl, _ = W.keys(seed32)
    emb = W.embed(ke, c)
    s = tokens.shape[1]
    pos = jnp.arange(s)
    x = emb[tokens] * c["embedding_multiplier"]

    def body(x, l):
        p = W.layer(kl, c, l)
        for grp in ("attn", "mlp"):
            p[grp] = {k: int4_matrix(v) for k, v in p[grp].items()}
        return block_apply(x, p, c, pos, served=True, plen=plen), None

    x, _ = jax.lax.scan(body, x, jnp.arange(c["num_hidden_layers"]))
    return head_logits(x, emb, W.final_norm(ke, c), c)[0]


@functools.partial(jax.jit, static_argnums=(1,))
def served_gaps(seed32, c, tokens, plen):
    """At every position i, the reference's best logit minus its logit of
    token i+1 (the served token)."""
    logits = served_logits(seed32, c, tokens, plen)
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    return gaps_from_logits(logits, nxt)


@functools.partial(jax.jit, static_argnums=(1,))
def control_gaps(seed32, c, tokens, plen):
    """``served_gaps``, and the control at every position: the reference's
    best logit minus its logit of the token that the float8-operand
    reference puts first."""
    logits = served_logits(seed32, c, tokens, plen)
    Precision.fp8 = True
    try:
        low = served_logits(seed32, c, tokens, plen)
    finally:
        Precision.fp8 = False
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    return (gaps_from_logits(logits, nxt),
            gaps_from_logits(logits, jnp.argmax(low, -1)))


def frozen(c: Dict):
    """The configuration's numbers as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def gaps_from_logits(logits, served):
    """Per position: best logit minus the served token's logit (>= 0)."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got
