"""Public wrapper: custom-VJP fused LOTION penalty.

``lotion_penalty_fused(w, fisher, fmt, block_size)`` returns the scalar
penalty; its backward uses the gradient computed IN THE SAME forward
kernel pass (saved as a residual), so the whole regularizer costs one
fused read of (w, fisher) + one write of grad per step.  Plugs into
``QuantConfig(use_kernel=True)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import CodebookFormat, get_format

from .lotion_reg import lotion_reg_pallas


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


def _fused(w, fisher, fmt_name: str, block_size: int):
    fmt = get_format(fmt_name)
    fp4 = isinstance(fmt, CodebookFormat)
    qmax = 6.0 if fp4 else float(fmt.qmax)
    shape = w.shape

    if block_size == -1:
        # per-matrix scales (matches core.quantize.matrix_axes semantics)
        from repro.core.quantize import _absmax_pertensor
        absmax = _absmax_pertensor(w)
        if absmax.size == 1:
            scale = jnp.where(absmax > 0, absmax / qmax, 1.0).reshape(())
            w2, n_pad = _to_2d(w, 1024)
            f2, _ = _to_2d(fisher, 1024)
            grad2, pen = lotion_reg_pallas(
                w2, f2, qmax=qmax, block_size=-1, fp4=fp4,
                scale=scale.astype(jnp.float32))
            flat = grad2.reshape(-1)
            if n_pad:
                flat = flat[:-n_pad]
            return jnp.sum(pen), flat.reshape(shape)
        # stacked leaf: vmap the per-matrix kernel over leading dims
        wm = w.reshape((-1,) + shape[-2:])
        fm = fisher.reshape((-1,) + shape[-2:])

        def one(wi, fi):
            p, g = _fused(wi, fi, fmt_name, -1)
            return p, g

        pens, grads = jax.vmap(one)(wm, fm)
        return jnp.sum(pens), grads.reshape(shape)

    w2, n_pad = _to_2d(w, block_size)
    f2, _ = _to_2d(fisher, block_size)
    grad2, pen = lotion_reg_pallas(w2, f2, qmax=qmax, block_size=block_size,
                                   fp4=fp4)
    flat = grad2.reshape(-1)
    if n_pad:
        flat = flat[:-n_pad]
    return jnp.sum(pen), flat.reshape(shape)


def lotion_penalty_fused_vg(w, fisher, fmt_name: str = "int4",
                            block_size: int = 256):
    """Fused (value, grad) in one kernel pass — the decoupled
    optimizer-side entry point: no custom_vjp detour, no autodiff
    re-traversal.  ``grad`` is the closed-form a.e. derivative
    ``1/2 fisher (lo + hi - 2w)`` with stop-gradded scale."""
    return _fused(w, fisher, fmt_name, block_size)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def lotion_penalty_fused(w, fisher, fmt_name: str = "int4",
                         block_size: int = 256):
    pen, _ = _fused(w, fisher, fmt_name, block_size)
    return pen


def _fwd(w, fisher, fmt_name, block_size):
    pen, grad = _fused(w, fisher, fmt_name, block_size)
    return pen, grad


def _bwd(fmt_name, block_size, grad, g):
    return (g * grad, jnp.zeros_like(grad))  # fisher is stop-gradded


lotion_penalty_fused.defvjp(_fwd, _bwd)
