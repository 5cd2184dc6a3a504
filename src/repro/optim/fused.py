"""Fused clip + decoupled-LOTION + {AdamW, SGD} optimizer cores.

``fused_lotion_adamw_core`` collapses the whole
``clip_global_norm -> lotion_decoupled -> adamw_core`` chain into ONE
terminal :class:`~repro.optim.transform.UpdateTransform` whose update is
a single Pallas kernel pass per leaf (``repro.kernels.opt_step``): one
read of (w, g, mu, nu), one write of (w', mu', nu'), and a per-tile
penalty partial.  ``fused_lotion_sgd_core`` is the same pass with the
SGD(+momentum) rule — the paper's synthetic experiments train with
SGD/GD, where ``fisher_decay`` maintains the g^2 EMA LOTION reads as the
Fisher diagonal f.  The only pre-pass left in either is the global-norm
reduction (clipping is global by definition — its elementwise *multiply*
fuses into the kernel as a scalar operand, the reduction cannot).

The cores have ``applies_updates=True``: they emit new PARAMETERS, not an
update step, so the train step skips ``apply_updates`` and the final
add-pass disappears too.  State is a flat dict
``{"mu", "nu", "count", "gnorm"[, "penalty"]}`` — ``penalty``/``gnorm``
are the same reserved metric keys the chain links use, so
``_link_metrics`` and the sharding rules treat fused and chained state
identically; ``penalty`` is present only when ``lam != 0`` (a lam=0
core under loss-side placement must not shadow the loss-aux penalty).
``mu``/``nu`` are always present (uniform sharding/checkpoint layout);
for SGD without momentum / without ``fisher_decay`` they stay zeros.

``use_kernel=False`` swaps the kernel for the pure-jnp oracle
(``kernels.opt_step.ref``) with identical call structure — the
bit-compatible fallback used off-TPU and in the kernel tests.

Not supported (``make_optimizer`` falls back to the unfused chain):
EF gradient compression (reorders the stream between clip and the
penalty), ``differentiate_scale=True`` (no closed form — loss-side
placement only, same rule as ``lotion_decoupled``), and LOTION-on-SGD
without ``fisher_decay`` (no Fisher estimate to weight the penalty).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy

from .clip import clip_scale, global_norm
from .transform import UpdateTransform


def _fused_core(kind: str, lr_fn, *, b1: float, b2: float, eps: float,
                weight_decay: float, momentum: float, fisher_decay,
                fmt_name: str, lam: float, block_size: int,
                clip_norm: float, policy: Optional[QuantPolicy],
                use_kernel: bool, meta: dict) -> UpdateTransform:
    policy = policy if policy is not None else QuantPolicy()

    def init(params):
        st = {
            "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32),
            "gnorm": jnp.zeros((), jnp.float32),
        }
        # the reserved "penalty" metric key exists ONLY when this core
        # owns a LOTION term — with lam=0 under loss-side placement the
        # real penalty flows through the loss aux, and a spurious 0 here
        # would clobber it in the train-step metrics
        if lam != 0.0:
            st["penalty"] = jnp.zeros((), jnp.float32)
        return st

    def update(grads, state, params=None, **extras):
        if params is None:
            raise ValueError(f"fused_lotion_{kind}_core needs params")
        norm = global_norm(grads)
        # non-finite guard (DESIGN.md §11): a poisoned step (non-finite
        # gnorm, or the train step's loss flag via the step_ok extra —
        # on a mesh that flag is already all-reduced across data shards
        # per DESIGN.md §12, so every device agrees before it gets here)
        # must apply NO update.  The gate rides INSIDE the step kernel
        # as the SC_OK scalar — w/mu/nu are written back unchanged with
        # zero extra HBM passes — and count is frozen here so the bias
        # corrections and lr schedule replay identically after a skip.
        ok = jnp.isfinite(norm)
        step_ok = extras.get("step_ok")
        if step_ok is not None:
            ok = jnp.logical_and(ok, step_ok)
        okf = ok.astype(jnp.float32)
        cscale = clip_scale(norm, clip_norm)
        count = state["count"] + 1
        if kind == "adamw":
            c = count.astype(jnp.float32)
            bc1 = 1 - b1 ** c
            bc2 = 1 - b2 ** c
        else:
            bc1 = bc2 = jnp.ones((), jnp.float32)
        lr = lr_fn(count)
        # transient LR backoff (spike-rollback cooldown) — a pure scalar
        # multiply, so it costs nothing fused into the kernel's lr slot
        lr_scale = extras.get("lr_scale")
        if lr_scale is not None:
            lr = lr * lr_scale

        if use_kernel:
            from repro.kernels.opt_step import (fused_opt_step_leaf as leaf_fn,
                                                fused_opt_step_sharded)
        else:
            from repro.kernels.opt_step import opt_step_ref as leaf_fn

        pens = []
        # the params' NamedShardings (make_train_step's grad_shardings): a
        # Mosaic kernel cannot be partitioned by GSPMD, so on a mesh of
        # more than one device each leaf's kernel runs per shard
        shardings = extras.get("shardings")

        def leaf(path, g, w, m, n, sh=None):
            leaf_lam = lam if (lam != 0.0 and policy.eligible(path, w)) else 0.0
            kw = dict(lr=lr, bc1=bc1, bc2=bc2, clip_scale=cscale,
                      lam=leaf_lam, fmt_name=fmt_name, block_size=block_size,
                      b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
                      core=kind, momentum=momentum, fisher_decay=fisher_decay,
                      ok=okf)
            if use_kernel and sh is not None and sh.mesh.size > 1:
                new_w, new_m, new_n, pen = fused_opt_step_sharded(
                    w, g, m, n, sh, **kw)
            else:
                new_w, new_m, new_n, pen = leaf_fn(w, g, m, n, **kw)
            if leaf_lam != 0.0:
                pens.append(pen.astype(jnp.float32))
            return (new_w, new_m, new_n)

        trees = (grads, params, state["mu"], state["nu"])
        if shardings is not None:
            trees += (shardings,)
        out = jax.tree_util.tree_map_with_path(leaf, *trees)
        new_params = jax.tree.map(lambda t: t[0], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
        new_mu = jax.tree.map(lambda t: t[1], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        new_nu = jax.tree.map(lambda t: t[2], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        # the metric scalars are gated like everything else: a skipped
        # step must leave the WHOLE opt state bit-identical (the chain
        # path gets the same via the train step's tree-wide select)
        new_state = {"mu": new_mu, "nu": new_nu,
                     "count": jnp.where(ok, count, state["count"]),
                     "gnorm": jnp.where(ok, norm, state["gnorm"])}
        if lam != 0.0:
            pen = (lam * jnp.sum(jnp.stack(pens)) if pens
                   else jnp.zeros((), jnp.float32))
            new_state["penalty"] = jnp.where(ok, pen, state["penalty"])
        return new_params, new_state

    def fisher(state):
        if kind == "sgd" and fisher_decay is None:
            return None               # nu is inert zeros, not a Fisher
        return state["nu"]

    return UpdateTransform(
        init=init, update=update, fisher=fisher,
        tag=f"fused_lotion_{kind}", applies_updates=True,
        meta={**meta, "lr_fn": lr_fn, "lam": lam, "fmt_name": fmt_name,
              "block_size": block_size, "clip_norm": clip_norm,
              "use_kernel": use_kernel, "policy": policy})


def fused_lotion_adamw_core(lr_fn, b1: float = 0.9, b2: float = 0.95,
                            eps: float = 1e-8, weight_decay: float = 0.0,
                            *, fmt_name: str = "int4", lam: float = 0.0,
                            block_size: int = -1,
                            clip_norm: float = float("inf"),
                            policy: Optional[QuantPolicy] = None,
                            use_kernel: bool = True) -> UpdateTransform:
    """One-pass fused optimizer step (terminal core, applies updates).

    ``lam == 0`` degenerates to fused clip+AdamW (no neighbor math in
    the kernel); with ``lam != 0`` eligible leaves additionally get the
    Eq. 3 closed-form LOTION gradient and the penalty metric.  The
    per-step scalars (lr, bias corrections, clip scale) are computed
    once outside and fed to every leaf kernel as one prefetched operand.
    """
    return _fused_core(
        "adamw", lr_fn, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        momentum=0.0, fisher_decay=None, fmt_name=fmt_name, lam=lam,
        block_size=block_size, clip_norm=clip_norm, policy=policy,
        use_kernel=use_kernel,
        meta={"kind": "fused_lotion_adamw", "b1": b1, "b2": b2, "eps": eps,
              "weight_decay": weight_decay})


def fused_lotion_sgd_core(lr_fn, momentum: float = 0.0,
                          fisher_decay: Optional[float] = None,
                          *, fmt_name: str = "int4", lam: float = 0.0,
                          block_size: int = -1,
                          clip_norm: float = float("inf"),
                          policy: Optional[QuantPolicy] = None,
                          use_kernel: bool = True) -> UpdateTransform:
    """One-pass fused clip + LOTION + SGD(+momentum) step (terminal core).

    The synthetic-experiment twin of :func:`fused_lotion_adamw_core`:
    bit-compatible with the unfused ``clip -> lotion_decoupled ->
    sgd_core`` chain.  A LOTION term (``lam != 0``) requires
    ``fisher_decay`` — SGD has no second moment, so the g^2 EMA is the
    only Fisher estimate for the penalty weighting.
    """
    if lam != 0.0 and fisher_decay is None:
        raise ValueError(
            "fused_lotion_sgd_core with lam != 0 needs fisher_decay: the "
            "LOTION penalty is Fisher-weighted and SGD tracks no second "
            "moment of its own")
    return _fused_core(
        "sgd", lr_fn, b1=0.0, b2=0.0, eps=0.0, weight_decay=0.0,
        momentum=momentum, fisher_decay=fisher_decay, fmt_name=fmt_name,
        lam=lam, block_size=block_size, clip_norm=clip_norm, policy=policy,
        use_kernel=use_kernel,
        meta={"kind": "fused_lotion_sgd", "momentum": momentum,
              "fisher_decay": fisher_decay})
