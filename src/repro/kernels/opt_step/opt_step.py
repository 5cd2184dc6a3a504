"""Fused optimizer-step kernel (pl.pallas_call + BlockSpec).

Two terminal cores share the pass (static ``core`` switch): ``"adamw"``
(the LM runs) and ``"sgd"`` with optional momentum + Fisher-EMA tracking
(the paper's synthetic experiments train with SGD/GD; ``fisher_decay``
maintains the g^2 EMA LOTION reads as f).  For AdamW, one pass per
(8x128-aligned) tile computes the ENTIRE per-step update rule of the
``clip -> lotion_decoupled -> adamw_core`` chain:

    gc   = g * clip_scale                       (global-norm clip)
    ct   = 1/2 lam f                            (f = pre-update nu)
    g'   = gc + ct (hi - w) - ct (w - lo)       (Eq. 3 closed-form grad)
    mu'  = b1 mu + (1-b1) g'
    nu'  = b2 nu + (1-b2) g'^2
    w'   = w - lr ((mu'/bc1) / (sqrt(nu'/bc2) + eps) + wd w)
    pen  = 1/2 sum f (hi - w)(w - lo)           (per-tile partial)

reading (w, g, mu, nu) once and writing (w', mu', nu') once — the
unfused chain makes ~8 separate tree-wide elementwise HBM passes for
the same math (mu EMA, nu EMA, AdamW step, weight decay, penalty grad,
clip multiply, apply_updates, penalty value), which is the whole cost
of the optimizer step in the paper's memory-bound 150M/300M LM regime.

Step scalars (lr, bias corrections, the clip scale, the per-matrix
quant scale and the step-ok guard flag) arrive as one prefetched (1, 8)
operand, the same pattern ``lotion_reg`` uses for its precomputed scale.

``scalars[SC_OK]`` is the on-device non-finite guard (DESIGN.md §11):
when 0 the kernel still makes its one read pass but writes back the
INPUT (w, mu, nu) unchanged — a poisoned step (NaN/inf loss or gnorm)
applies no update without any extra HBM pass, and without the host ever
having to inspect the gradients.  The select is elementwise in VMEM
(``jnp.where`` on the already-loaded tiles), so the kernel's DMA
contract (reads/writes per tile) is untouched.

Penalty modes (static):
* ``"scalar"`` — per-matrix scale passed in ``scalars[SC_SCALE]``
  (paper's per-tensor LLM setting, ``block_size == -1``).
* ``"block"``  — in-tile blockwise absmax (``block_size | tile_n``).
* ``"none"``   — no LOTION term (non-eligible leaves / ``lam == 0``):
  pure fused clip+AdamW, no neighbor math at all.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.lotion_reg.lotion_reg import (_blockwise_neighbors,
                                                _neighbors_fp4,
                                                _neighbors_int,
                                                pen_spec_and_shape,
                                                tile_partial)

# scalar-operand layout (one (1, 8) f32 row, lane-aligned)
SC_LR, SC_BC1, SC_BC2, SC_CLIP, SC_SCALE, SC_OK = 0, 1, 2, 3, 4, 5
N_SCALARS = 8


def _opt_kernel(w_ref, g_ref, mu_ref, nu_ref, sc_ref,
                w_out, mu_out, nu_out, pen_ref, *,
                b1, b2, eps, wd, lam, qmax, bs, fp4, penalty_mode,
                core, momentum, fisher_decay):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    nu = nu_ref[...].astype(jnp.float32)
    lr = sc_ref[0, SC_LR]
    bc1 = sc_ref[0, SC_BC1]
    bc2 = sc_ref[0, SC_BC2]

    g = g * sc_ref[0, SC_CLIP]

    if penalty_mode == "none":
        pen_ref[...] = jnp.zeros_like(pen_ref)
    else:
        if penalty_mode == "scalar":
            s = sc_ref[0, SC_SCALE]
            lo, hi = (_neighbors_fp4(w, s) if fp4
                      else _neighbors_int(w, s, qmax))
        else:  # "block": shared in-tile scale convention with lotion_reg
            lo, hi = _blockwise_neighbors(w, bs, qmax, fp4)
        # exact float expression of lotion_penalty_and_grad (lam folded
        # into the cotangent first) — f is the PRE-update nu
        ct = (0.5 * lam) * nu
        g = g + (ct * (hi - w) - ct * (w - lo))
        pen_ref[...] = tile_partial(0.5 * (nu * ((hi - w) * (w - lo))))

    if core == "adamw":
        mu2 = b1 * mu + (1 - b1) * g
        nu2 = b2 * nu + (1 - b2) * g * g
        upd = (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + eps)
        new_w = w - lr * (upd + wd * w)
    else:  # "sgd": the paper's synthetic-experiment optimizer — nu is a
        # pure Fisher EMA (LOTION's f), never a step denominator
        nu2 = (fisher_decay * nu + (1 - fisher_decay) * g * g
               if fisher_decay is not None else nu)
        if momentum:
            mu2 = momentum * mu + g
            step = mu2
        else:
            mu2 = mu
            step = g
        new_w = w - lr * step
    # non-finite guard: ok=0 writes the inputs back untouched (NaN/inf in
    # the untaken branch is discarded by the select, never stored)
    ok = sc_ref[0, SC_OK] != 0.0
    w_out[...] = jnp.where(ok, new_w, w).astype(w_out.dtype)
    mu_out[...] = jnp.where(ok, mu2, mu).astype(mu_out.dtype)
    nu_out[...] = jnp.where(ok, nu2, nu).astype(nu_out.dtype)


def opt_step_pallas(w2d, g2d, mu2d, nu2d, scalars, *,
                    qmax: float, block_size: int, fp4: bool,
                    penalty_mode: str, b1: float, b2: float, eps: float,
                    weight_decay: float, lam: float,
                    core: str = "adamw", momentum: float = 0.0,
                    fisher_decay=None,
                    tile_m: int = 8, tile_n: int = 1024,
                    interpret: Optional[bool] = None):
    """Fused step over a 2-D leaf view.

    Returns ``(new_w (R, C), new_mu, new_nu, pen_partials)`` (the penalty
    is the sum of ``pen_partials``);
    ``scalars`` is the (1, 8) [lr, bc1, bc2, clip_scale, scale, ok, ...]
    row (``ok`` = the non-finite guard flag; 0 freezes w/mu/nu in-kernel).
    """
    R, C = w2d.shape
    tile_n = min(tile_n, C)
    tile_m = min(tile_m, R)
    assert R % tile_m == 0 and C % tile_n == 0, (R, C, tile_m, tile_n)
    if penalty_mode == "block":
        assert tile_n % block_size == 0, (tile_n, block_size)
    assert scalars.shape == (1, N_SCALARS), scalars.shape
    grid = (R // tile_m, C // tile_n)

    tile = pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j))
    sc_spec = pl.BlockSpec((1, N_SCALARS), lambda i, j: (0, 0))
    pen_spec, pen_shape = pen_spec_and_shape(grid)
    out_shape = (jax.ShapeDtypeStruct((R, C), w2d.dtype),
                 jax.ShapeDtypeStruct((R, C), mu2d.dtype),
                 jax.ShapeDtypeStruct((R, C), nu2d.dtype),
                 pen_shape)

    kern = functools.partial(
        _opt_kernel, b1=b1, b2=b2, eps=eps, wd=weight_decay, lam=lam,
        qmax=qmax, bs=block_size, fp4=fp4, penalty_mode=penalty_mode,
        core=core, momentum=momentum, fisher_decay=fisher_decay)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[tile, tile, tile, tile, sc_spec],
        out_specs=(tile, tile, tile, pen_spec),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret), name="opt_step",
    )(w2d, g2d, mu2d, nu2d, scalars)
