"""Public wrapper: fused optimizer step for one parameter leaf.

Handles the leaf -> (R, C) tiling (same layout contract as the
``lotion_reg`` wrapper, so the blockwise view matches
``core.quantize._block_view`` and the per-matrix scale matches
``matrix_axes`` semantics), stacks the step scalars into the kernel's
prefetched (1, 8) operand, and vmaps the per-matrix kernel over the
leading dims of stacked leaves.

Zero padding is inert through the WHOLE fused rule: padded w = g = mu =
nu = 0 gives lo = hi = 0, penalty grad 0, mu' = nu' = 0, update 0 and
w' = 0, so slicing the pad off afterwards recovers the exact unpadded
result (asserted against the oracle in tests/test_opt_step.py).

``fused_opt_step_sharded`` runs the same step on a leaf sharded over a
mesh: GSPMD cannot partition a Mosaic kernel, so every device runs it on
its own shard under ``shard_map``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.formats import CodebookFormat, get_format
from repro.core.quantize import _absmax_pertensor
from repro.kernels.lotion_reg.ops import _to_2d

from .opt_step import N_SCALARS, opt_step_pallas


def pertensor_scale(w, qmax: float):
    """Per-matrix quant scale of ``w`` (keepdims absmax / qmax, 1 for an
    all-zero matrix) — a plain reduction, so a sharded ``w`` reduces
    per shard plus a small all-reduce."""
    absmax = _absmax_pertensor(w)
    return jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)


def _scalars_row(lr, bc1, bc2, clip_scale, scale, ok=1.0):
    row = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(bc1, jnp.float32),
        jnp.asarray(bc2, jnp.float32), jnp.asarray(clip_scale, jnp.float32),
        jnp.asarray(scale, jnp.float32), jnp.asarray(ok, jnp.float32)])
    return jnp.concatenate(
        [row, jnp.zeros((N_SCALARS - row.shape[0],), jnp.float32)]
    ).reshape(1, N_SCALARS)


def fused_opt_step_leaf(w, g, mu, nu, *, lr, bc1, bc2, clip_scale,
                        lam: float, fmt_name: str, block_size: int,
                        b1: float, b2: float, eps: float,
                        weight_decay: float, core: str = "adamw",
                        momentum: float = 0.0, fisher_decay=None,
                        ok=None, scale=None, interpret=None):
    """One fused (clip + LOTION + AdamW/SGD) step for one leaf.

    Returns ``(new_w, new_mu, new_nu, pen)`` with ``pen`` the UNSCALED
    penalty scalar (0 for ``lam == 0``).  ``lr``/``bc1``/``bc2``/
    ``clip_scale`` are traced step scalars; everything else is static.
    ``core="sgd"`` ignores b1/b2/eps/weight_decay/bc* and uses
    ``momentum``/``fisher_decay`` instead (pass ``bc1=bc2=1.0``).
    ``ok`` (traced 0/1 scalar, default 1) is the non-finite guard: 0
    makes the kernel write (w, mu, nu) back unchanged — the skip path of
    a poisoned step, gated INSIDE the kernel so no extra HBM pass exists
    on either branch.  The caller owns the flag's scope: under GSPMD the
    train step all-reduces it across data shards first (DESIGN.md §12),
    so by kernel entry every device holds the same 0/1.
    ``scale`` (per-tensor mode only) is the precomputed per-matrix quant
    scale, shaped like the keepdims absmax; default: computed from ``w``.
    """
    ok = 1.0 if ok is None else ok
    fmt = get_format(fmt_name)
    fp4 = isinstance(fmt, CodebookFormat)
    qmax = 6.0 if fp4 else float(fmt.qmax)
    shape = w.shape
    hyper = dict(qmax=qmax, fp4=fp4, b1=b1, b2=b2, eps=eps,
                 weight_decay=weight_decay, lam=lam, core=core,
                 momentum=momentum, fisher_decay=fisher_decay,
                 interpret=interpret)

    def run_2d(c_width, scale, penalty_mode, args):
        tiled = [_to_2d(x, c_width) for x in args]
        n_pad = tiled[0][1]
        scalars = _scalars_row(lr, bc1, bc2, clip_scale, scale, ok)
        w2, mu2, nu2, pen = opt_step_pallas(
            tiled[0][0], tiled[1][0], tiled[2][0], tiled[3][0], scalars,
            block_size=(block_size if penalty_mode == "block" else -1),
            penalty_mode=penalty_mode, **hyper)

        def unpad(x2):
            flat = x2.reshape(-1)
            if n_pad:
                flat = flat[:-n_pad]
            return flat.reshape(shape)

        return unpad(w2), unpad(mu2), unpad(nu2), jnp.sum(pen)

    if lam == 0.0:
        return run_2d(1024, 0.0, "none", (w, g, mu, nu))

    if block_size == -1:
        if scale is None:
            scale = pertensor_scale(w, qmax)
        if scale.size == 1:
            return run_2d(1024, scale.reshape(()).astype(jnp.float32),
                          "scalar", (w, g, mu, nu))
        # stacked leaf: one scale per trailing matrix — vmap the
        # per-matrix kernel over the flattened leading dims
        mats = [x.reshape((-1,) + shape[-2:]) for x in (w, g, mu, nu)]

        def one(wi, gi, mi, ni, si):
            return fused_opt_step_leaf(
                wi, gi, mi, ni, lr=lr, bc1=bc1, bc2=bc2,
                clip_scale=clip_scale, lam=lam, fmt_name=fmt_name,
                block_size=-1, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay, core=core, momentum=momentum,
                fisher_decay=fisher_decay, ok=ok, scale=si,
                interpret=interpret)

        nw, nm, nn, pens = jax.vmap(one)(*mats, scale.reshape(-1))
        return (nw.reshape(shape), nm.reshape(shape), nn.reshape(shape),
                jnp.sum(pens))

    return run_2d(block_size, 0.0, "block", (w, g, mu, nu))


def fused_opt_step_sharded(w, g, mu, nu, sharding, *, lr, bc1, bc2,
                           clip_scale, lam: float, fmt_name: str,
                           block_size: int, ok=None, **hyper):
    """:func:`fused_opt_step_leaf` on a leaf placed per ``sharding`` (a
    ``NamedSharding``; ``g``/``mu``/``nu`` share it).

    GSPMD cannot partition a Mosaic kernel, so each device runs the step
    on its own shard under ``shard_map``.  The rule is elementwise except
    for two whole-matrix terms: the per-tensor LOTION scale is reduced
    outside (a sharded max) and passed in, and the penalty partials are
    summed over the mesh axes the leaf is split on (replicated axes hold
    identical copies and are not summed).  Blockwise scales need every
    quant block inside one shard.
    """
    mesh, spec = sharding.mesh, sharding.spec
    spec = tuple(spec) + (None,) * (w.ndim - len(tuple(spec)))
    split = tuple(a for entry in spec if entry is not None
                  for a in ((entry,) if isinstance(entry, str) else entry))
    if block_size == -1 and lam != 0.0:
        fmt = get_format(fmt_name)
        qmax = 6.0 if isinstance(fmt, CodebookFormat) else float(fmt.qmax)
        scale = pertensor_scale(w, qmax)
        scale_spec = P(*(spec[:-2] + (None, None))) if w.ndim >= 2 else P()
    else:
        scale, scale_spec = jnp.zeros((), jnp.float32), P()
    ok = 1.0 if ok is None else ok
    scalars = jnp.stack([jnp.asarray(x, jnp.float32)
                         for x in (lr, bc1, bc2, clip_scale, ok)])

    def local(w, g, mu, nu, sc, scale):
        nw, nm, nn, pen = fused_opt_step_leaf(
            w, g, mu, nu, lr=sc[0], bc1=sc[1], bc2=sc[2], clip_scale=sc[3],
            ok=sc[4], lam=lam, fmt_name=fmt_name, block_size=block_size,
            scale=scale if scale.ndim else None, **hyper)
        return nw, nm, nn, jax.lax.psum(pen, split) if split else pen

    leaf = P(*spec)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(leaf,) * 4 + (P(), scale_spec),
        out_specs=(leaf,) * 3 + (P(),), check_vma=False)(
        w, g, mu, nu, scalars, scale)
