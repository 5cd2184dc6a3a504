"""Training loop: step construction (quant modes + LOTION penalty +
microbatching + clipping + EF compression), quantized evaluation, and the
fault-tolerant driver loop.

The step is built on a composable update-transform chain
(:mod:`repro.optim.transform`)::

    grads -> clip -> [ef_compress] -> [lotion_decoupled] -> optimizer core

:func:`make_optimizer` assembles the chain from a ``TrainConfig`` and a
base optimizer; :func:`make_train_step` only computes gradients (the
microbatch scan) and runs the chain.  With the default
``penalty_placement="decoupled"``, the LOTION penalty is applied via its
closed-form gradient exactly once per step — outside the microbatch scan
and outside clipping (DESIGN.md §2); ``penalty_placement="loss"`` keeps
the seed-era loss-side behavior.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import QuantConfig, cast_params, forward_params, penalty
from repro.models.lm import LMConfig, lm_forward
from repro.optim import (UpdateTransform, as_transform, apply_updates, chain,
                         clip_global_norm, fused_lotion_adamw_core,
                         fused_lotion_sgd_core,
                         global_norm, lotion_decoupled)
from repro.train.compress import ef_transform
from repro.train.guard import (NonFiniteBudgetError, RollbackBudgetError,
                               SpikeMonitor)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    clip_norm: float = 1.0
    n_microbatches: int = 1
    ef_compress: bool = False
    ef_block: int = 256
    seed: int = 0
    attn_chunk: int = 0      # 0 = full-score attention; >0 = streaming chunks
    logit_chunk: int = 0     # 0 = full logits; >0 = chunked head+CE (remat)
    # None = follow quant.penalty_placement; "loss"/"decoupled" overrides
    penalty_placement: Optional[str] = None

    def __post_init__(self):
        from repro.core.modes import PENALTY_PLACEMENTS
        if (self.penalty_placement is not None
                and self.penalty_placement not in PENALTY_PLACEMENTS):
            raise ValueError(
                f"penalty_placement {self.penalty_placement!r} not in "
                f"{PENALTY_PLACEMENTS} (or None to follow quant config)")

    @property
    def placement(self) -> str:
        return self.penalty_placement or self.quant.penalty_placement


def make_optimizer(tcfg: TrainConfig, base) -> UpdateTransform:
    """Assemble the per-step update chain from a base optimizer.

    ``base`` may be an :class:`UpdateTransform` core, a back-compat
    ``Optimizer`` wrapper (its ``.transform`` core is used), or an already
    assembled chain (``links`` set) which passes through untouched.  Use
    the returned transform for BOTH ``init_state`` and
    ``make_train_step`` — the chain owns clip/EF/penalty state.

    When the quant config resolves ``use_kernel`` true (auto on TPU) and
    the base core is AdamW, the whole ``clip -> [lotion] -> adamw`` chain
    collapses into :func:`~repro.optim.fused_lotion_adamw_core` — one
    Pallas kernel pass per leaf instead of ~8 tree-wide elementwise HBM
    passes (DESIGN.md §5).  The unfused jnp chain stays the
    bit-compatible fallback: EF compression, ``differentiate_scale`` and
    loss-side lotion placement all route through it.
    """
    base_t = as_transform(base)
    q = tcfg.quant
    wants_lotion = (q.method == "lotion" and q.lam != 0.0
                    and tcfg.placement == "decoupled")
    if base_t.links is not None:
        # pre-assembled chain: used as-is, but it must agree with tcfg on
        # the penalty placement — a mismatch would silently train without
        # (or doubly with) the regularizer
        has_lotion = any(t.tag == "lotion_decoupled" for t in base_t.links)
        if wants_lotion and not has_lotion:
            raise ValueError(
                "pre-assembled chain has no lotion_decoupled link but the "
                "train config wants the decoupled LOTION penalty — build "
                "the chain with make_optimizer, or add the link")
        if has_lotion and not wants_lotion:
            raise ValueError(
                "pre-assembled chain contains a lotion_decoupled link but "
                "the train config does not use the decoupled placement — "
                "the penalty would be double-counted or misconfigured")
        return base_t
    if base_t.applies_updates:
        # pre-built fused core: passes through, but every baked-in config
        # value the train config also carries must agree (same
        # no-silent-misconfig rule as above)
        meta = base_t.meta or {}
        has_lotion = meta.get("lam", 0.0) != 0.0
        if wants_lotion and not has_lotion:
            raise ValueError(
                "pre-built fused core has lam=0 but the train config wants "
                "the decoupled LOTION penalty — build it with make_optimizer")
        if has_lotion and not wants_lotion:
            raise ValueError(
                "pre-built fused core carries a LOTION term the train "
                "config does not use — the penalty would be misconfigured")
        checks = [("clip_norm", tcfg.clip_norm),
                  ("use_kernel", q.kernel_enabled)]
        if has_lotion:
            checks += [("lam", q.lam), ("fmt_name", q.fmt_name),
                       ("block_size", q.block_size), ("policy", q.policy)]
        for key, want in checks:
            if key in meta and meta[key] != want:
                raise ValueError(
                    f"pre-built fused core was built with {key}="
                    f"{meta[key]!r} but the train config says {want!r} — "
                    f"rebuild it with make_optimizer")
        if tcfg.ef_compress:
            raise ValueError(
                "EF compression cannot be fused — drop the pre-built "
                "fused core and let make_optimizer assemble the chain")
        return base_t
    if wants_lotion and q.differentiate_scale:
        raise ValueError(
            "decoupled LOTION has no closed form for a differentiable "
            "scale; use penalty_placement='loss' with "
            "differentiate_scale=True")

    # fused core selection: collapse clip -> [lotion] -> {adamw, sgd}
    # into the single-pass step kernel.  The loss-side placement keeps
    # the penalty in the loss, so the fused core then runs with lam=0
    # (plain clip+core fusion).  LOTION-on-SGD fuses only when the core
    # tracks a Fisher EMA (fisher_decay) — without one there is no f to
    # weight the penalty, fused or not.
    meta = base_t.meta or {}
    can_fuse = (q.kernel_enabled and not tcfg.ef_compress
                and (meta.get("kind") == "adamw"
                     or (meta.get("kind") == "sgd"
                         and (not wants_lotion
                              or meta.get("fisher_decay") is not None))))
    if can_fuse and meta["kind"] == "adamw":
        return fused_lotion_adamw_core(
            meta["lr_fn"], b1=meta["b1"], b2=meta["b2"], eps=meta["eps"],
            weight_decay=meta["weight_decay"], fmt_name=q.fmt_name,
            lam=(q.lam if wants_lotion else 0.0), block_size=q.block_size,
            clip_norm=tcfg.clip_norm, policy=q.policy)
    if can_fuse:
        return fused_lotion_sgd_core(
            meta["lr_fn"], momentum=meta["momentum"],
            fisher_decay=meta["fisher_decay"], fmt_name=q.fmt_name,
            lam=(q.lam if wants_lotion else 0.0), block_size=q.block_size,
            clip_norm=tcfg.clip_norm, policy=q.policy)

    links = [clip_global_norm(tcfg.clip_norm)]
    if tcfg.ef_compress:
        links.append(ef_transform(tcfg.ef_block))
    if wants_lotion:
        links.append(lotion_decoupled(q.fmt_name, q.lam, q.block_size,
                                      use_kernel=q.kernel_enabled,
                                      policy=q.policy))
    links.append(base_t)
    return chain(*links)


def _ce_terms(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-position CE terms ``logz - gold`` with the logits' leading
    shape (b, l[, c]) — :func:`cross_entropy` is their mean, and the
    per-example CE vector (the cross-shard gate's raw material) is their
    mean over the non-batch axes.  One set of elementwise terms feeds
    both, so the side output cannot perturb the scalar's bits."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    return logz - gold


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE in nats.  logits: (b, l, [c,] v) fp32; labels: (b, l[, c]).

    The gold logit is extracted with an iota==label mask (not
    take_along_axis): elementwise on the logits layout, so a vocab-sharded
    logits tensor stays sharded and the reduction lowers to one small
    all-reduce under GSPMD instead of an all-gather of the logits.
    """
    return jnp.mean(_ce_terms(logits, labels))


def make_loss_fn(cfg: LMConfig, tcfg: TrainConfig):
    from repro.models.lm import lm_loss

    loss_side = tcfg.placement == "loss"

    def loss_fn(params, batch, fisher, rng):
        fwd = forward_params(tcfg.quant, params, rng)
        ce, ce_ex = lm_loss(fwd, cfg, batch["tokens"], batch["labels"],
                            image_embeds=batch.get("image_embeds"),
                            attn_chunk=tcfg.attn_chunk or None,
                            logit_chunk=tcfg.logit_chunk or None,
                            per_example=True)
        # ce_ex rides the aux dict to make_train_step, which pops it and
        # folds all(isfinite(ce_ex)) into the skip gate — the explicit
        # cross-data-shard agreement on "was this step poisoned"
        aux = {"ce": ce, "ce_ex": ce_ex}
        if loss_side:
            pen = penalty(tcfg.quant, params, fisher)
            aux["penalty"] = pen
            return ce + pen, aux
        # decoupled placement: the penalty never touches the loss — it is
        # applied once per step by the lotion_decoupled chain link
        return ce, aux
    return loss_fn


def _link_metrics(opt_state, out=None) -> Dict[str, jnp.ndarray]:
    """Collect per-link metric scalars ("gnorm", "penalty") from (possibly
    nested) chain state.  Trace-time Python over the pytree containers."""
    out = {} if out is None else out
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            _link_metrics(s, out)
    elif isinstance(opt_state, dict):
        for key in ("gnorm", "penalty"):
            if key in opt_state:
                out[key] = opt_state[key]
    return out


def make_train_step(cfg: LMConfig, tcfg: TrainConfig, optimizer,
                    loss_fn: Optional[Callable] = None,
                    grad_shardings=None):
    """Returns ``train_step(state, batch) -> (state, metrics)`` (jit-able,
    pjit-compatible: all collectives emerge from GSPMD sharding).

    ``optimizer`` is anything :func:`make_optimizer` accepts; the SAME
    chain must have produced ``state["opt"]`` (build it once, pass it to
    both ``init_state`` and here).

    ``grad_shardings``: optional pytree of NamedSharding matching params;
    constrains the gradient tree (and hence the scan-backward gradient
    accumulators, via backward propagation into the loop carry) — without
    it GSPMD can leave stacked-layer gradients replicated, blowing HBM.
    """
    tx = make_optimizer(tcfg, optimizer)
    loss_fn = loss_fn or make_loss_fn(cfg, tcfg)

    def train_step(state, batch):
        params = state["params"]
        rng = jax.random.fold_in(jax.random.PRNGKey(tcfg.seed), state["step"])
        # pre-update Fisher (AdamW's nu), read through the chain — the same
        # f both penalty placements see
        fisher = tx.fisher(state["opt"])
        if fisher is None:
            fisher = jax.tree.map(jnp.zeros_like, params)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if tcfg.n_microbatches > 1:
            def micro(c, mb):
                (l, aux), g = grad_fn(params, mb, fisher, rng)
                acc_l, acc_g = c
                return (acc_l + l, jax.tree.map(jnp.add, acc_g, g)), aux

            n = tcfg.n_microbatches
            mbs = jax.tree.map(
                lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)
            zero_g = jax.tree.map(jnp.zeros_like, params)
            (loss, grads), auxs = jax.lax.scan(micro, (0.0, zero_g), mbs)
            loss = loss / n
            grads = jax.tree.map(lambda g: g / n, grads)
            auxs = dict(auxs)
            ce_ex = auxs.pop("ce_ex", None)  # (n, b/n) — keep raw terms
            aux = jax.tree.map(lambda a: a.mean(), auxs)
        else:
            (loss, aux), grads = grad_fn(params, batch, fisher, rng)
            aux = dict(aux)
            ce_ex = aux.pop("ce_ex", None)

        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)

        # on-device non-finite guard (DESIGN.md §11): ok_loss gates the
        # update through the chain — a fused core folds it into its
        # in-kernel SC_OK gate (together with its own gnorm check), the
        # jnp chain is gated below with a tree-wide where.  lr_scale is
        # run_loop's spike-cooldown backoff (absent => no-op).
        #
        # Globally consistent skip gate (DESIGN.md §12): under a data/pod
        # mesh the scalar loss is already the cross-shard mean, but
        # folding all(isfinite(ce_ex)) — the per-example CE terms — in as
        # well makes the agreement explicit and lowers to one extra small
        # all-reduce.  On a 1x1 mesh it is bit-exact with isfinite(loss)
        # alone: any non-finite per-example term makes the IEEE mean
        # non-finite, and a finite-terms overflow trips isfinite(loss) in
        # both forms.  Every shard computes the same boolean, so a NaN on
        # ONE data shard skips the step on ALL shards — no replica can
        # apply an update its peers skipped.
        ok_loss = jnp.isfinite(loss)
        if ce_ex is not None:
            ok_loss = jnp.logical_and(ok_loss,
                                      jnp.all(jnp.isfinite(ce_ex)))
        updates, new_opt = tx.update(grads, state["opt"], params,
                                     fisher=fisher, step_ok=ok_loss,
                                     lr_scale=state.get("lr_scale"),
                                     shardings=grad_shardings)

        link = _link_metrics(new_opt)
        gnorm = link.get("gnorm")
        if gnorm is None:
            gnorm = global_norm(grads)
        ok = jnp.logical_and(ok_loss, jnp.isfinite(gnorm))

        if tx.applies_updates:
            # a fused terminal core emits new params straight from the
            # step kernel (update already SC_OK-gated inside it; adding a
            # tree-wide select here would re-introduce the extra HBM pass
            # the fusion removed)
            new_params, gated_opt = updates, new_opt
        else:
            # jnp chain: select per leaf — a skipped step keeps params
            # AND the whole chain state (moments, count, EF error) so the
            # replayed schedule is bit-identical to never having seen the
            # poisoned batch
            def sel(new, old):
                return jnp.where(ok, new, old)

            new_params = jax.tree.map(sel, apply_updates(params, updates),
                                      params)
            gated_opt = jax.tree.map(sel, new_opt, state["opt"])

        new_state = dict(state)
        new_state.update(params=new_params, opt=gated_opt,
                         step=state["step"] + 1)

        metrics = {"loss": loss, **aux}
        metrics["grad_norm"] = gnorm
        # the guard flag ships on the existing metrics transfer — no
        # extra device sync to learn a step was poisoned
        metrics["skipped"] = jnp.logical_not(ok)
        if "penalty" in link:       # decoupled placement
            metrics["penalty"] = link["penalty"]
            metrics["loss"] = loss + link["penalty"]
        return new_state, metrics

    return train_step


# --------------------------------------------------------------------------
# Quantized evaluation (the paper's headline metric)
# --------------------------------------------------------------------------

def make_eval_fn(cfg: LMConfig, qcfg: QuantConfig):
    """Returns eval_fn(params, batch, mode, key) -> CE, where mode selects
    fp32 / RTN-quantized / RR-rounded parameters."""

    def eval_fn(params, batch, mode: str = "fp32", key=None):
        if mode == "fp32":
            p = params
        else:
            p = cast_params(params, qcfg.fmt, qcfg.policy, qcfg.block_size,
                            mode=mode, key=key)
        logits = lm_forward(p, cfg, batch["tokens"],
                            image_embeds=batch.get("image_embeds"))
        return cross_entropy(logits, batch["labels"])

    return eval_fn


# --------------------------------------------------------------------------
# Driver loop with telemetry + checkpoint/restart hooks
# --------------------------------------------------------------------------

# step-time telemetry window: percentiles look at <= the last 200 entries,
# so a bounded deque keeps week-long runs from growing an unbounded list
TELEMETRY_WINDOW = 200


def _eval_scalar(ev):
    """Scalar CE out of an ``eval_hook`` result for the eval-side
    :class:`SpikeMonitor`: a bare number (or 0-d array) passes through; a
    dict prefers the conventional CE keys, then the first value that
    coerces to float.  ``None`` when nothing numeric is found — the
    monitor simply never observes that eval."""
    if isinstance(ev, dict):
        keys = [k for k in ("ce", "eval_ce", "ce_fp32", "loss") if k in ev]
        candidates = [ev[k] for k in keys] or list(ev.values())
    else:
        candidates = [ev]
    for v in candidates:
        try:
            return float(v)
        except (TypeError, ValueError):
            continue
    return None


def opt_state_is_fused(opt_state) -> bool:
    """True iff ``state["opt"]`` came from the fused single-pass core
    (flat dict carrying both moments AND the metric scalars) rather than
    an update-transform chain (tuple of link states)."""
    return (isinstance(opt_state, dict) and "gnorm" in opt_state
            and "mu" in opt_state)


def run_loop(train_step, state, pipeline, n_steps: int,
             eval_every: int = 0, eval_hook: Optional[Callable] = None,
             ckpt_every: int = 0, ckpt_hook: Optional[Callable] = None,
             log_every: int = 50, log: Callable = print,
             straggler_pct: float = 95.0,
             ckpt_dir: Optional[str] = None, ckpt_keep: int = 3,
             ckpt_shards: int = 1,
             auto_resume: bool = False,
             max_skips: int = 8,
             spike_zscore: float = 0.0, spike_ema: float = 0.98,
             spike_patience: int = 2, spike_warmup: int = 8,
             eval_spike_zscore: float = 0.0, eval_spike_ema: float = 0.9,
             eval_spike_patience: int = 1, eval_spike_warmup: int = 4,
             backoff_scale: float = 0.5, cooldown_steps: int = 16,
             max_rollbacks: int = 4,
             rollback_reorder: bool = True,
             coordinator=None,
             step_hook: Optional[Callable] = None) -> Dict[str, Any]:
    """Self-healing driver: telemetry, periodic eval + checkpoint, and the
    three recovery tiers of DESIGN.md §11.

    * **Skip budget** — ``train_step``'s non-finite guard already froze
      params/opt on a poisoned step; the loop counts CONSECUTIVE skipped
      steps and raises :class:`NonFiniteBudgetError` (with loss/gnorm
      diagnostics) past ``max_skips`` instead of spinning forever.
    * **Spike rollback** — with ``spike_zscore > 0`` (requires
      ``ckpt_dir``), a :class:`SpikeMonitor` watches the loss; on a
      sustained spike the loop restores the newest VALID checkpoint,
      rewinds the data stream via ``pipeline.seek`` (exact batch replay —
      batches are pure functions of the step index), and applies an LR
      backoff of ``backoff_scale`` for ``cooldown_steps`` steps through
      ``state["lr_scale"]`` (a traced scalar: no recompile).  More than
      ``max_rollbacks`` raises :class:`RollbackBudgetError`.
    * **Auto-resume** — ``auto_resume=True`` (requires ``ckpt_dir``)
      restores the newest checkpoint whose manifest CRC verifies,
      quarantining corrupt ones, then seeks the pipeline; combined with
      the step-indexed rng (``fold_in(seed, step)``) the continued run is
      bit-identical to one that never crashed.

    Distributed self-healing (DESIGN.md §12) extends each tier across
    hosts:

    * ``coordinator`` — a :class:`~repro.distributed.Coordinator`; every
      host-level decision (which checkpoint to restore, the rollback
      target, the data seek index) goes through an agreement round, so a
      host can never roll back alone.  The default single-host
      coordinator makes every round trivially unanimous — behavior and
      bits identical to the pre-distributed loop.
    * ``ckpt_shards`` — saves write that many payload shards per step; a
      step is restorable only if EVERY shard verifies (one torn shard
      quarantines the whole step on all hosts, via the election's min).
    * ``rollback_reorder=True`` — a rollback replays with DIFFERENT data:
      the pipeline seeks PAST the window that fed the spike (offset
      accumulates across rollbacks), counted in
      ``data_windows_skipped``.  ``False`` restores the exact-replay
      behavior (same batches, reduced LR).
    * ``eval_spike_zscore > 0`` — a second :class:`SpikeMonitor` watches
      the scalar eval CE (own warmup/patience, tuned for the much rarer
      eval cadence); a sustained eval-loss spike triggers the same
      coordinated rollback, counted in ``eval_rollbacks``.

    ``ckpt_dir`` enables the loop's own atomic checkpointing every
    ``ckpt_every`` steps (``ckpt_hook`` remains for callers doing their
    own persistence; both may be used together).  ``step_hook(state,
    metrics)`` runs after every step — the chaos harness's crash seam.

    Returns ``{"state", "history", "step_times", "skipped", "rollbacks",
    "eval_rollbacks", "data_windows_skipped", "resumed_from"}`` — the
    same counters the periodic log line prints, so bench logs and the
    chaos auditor read one source of truth.
    """
    from repro.checkpoint import io as ckpt_io
    from repro.distributed.coordinator import Coordinator

    coord = coordinator if coordinator is not None else Coordinator()
    spiking = spike_zscore > 0.0
    eval_spiking = eval_spike_zscore > 0.0
    any_spiking = spiking or eval_spiking
    if any_spiking and not ckpt_dir:
        raise ValueError("spike rollback (spike/eval_spike_zscore > 0) "
                         "needs ckpt_dir")
    if eval_spiking and not (eval_every and eval_hook):
        raise ValueError("eval spike monitor (eval_spike_zscore > 0) "
                         "needs eval_every and eval_hook")
    if auto_resume and not ckpt_dir:
        raise ValueError("auto_resume needs ckpt_dir")
    monitor = (SpikeMonitor(zscore=spike_zscore, ema=spike_ema,
                            patience=spike_patience, warmup=spike_warmup)
               if spiking else None)
    eval_monitor = (SpikeMonitor(zscore=eval_spike_zscore,
                                 ema=eval_spike_ema,
                                 patience=eval_spike_patience,
                                 warmup=eval_spike_warmup)
                    if eval_spiking else None)
    if any_spiking and "lr_scale" not in state:
        state = dict(state)
        state["lr_scale"] = jnp.ones((), jnp.float32)
    template = jax.eval_shape(lambda: state)
    counters: Dict[str, Any] = {"skipped": 0, "rollbacks": 0,
                                "eval_rollbacks": 0,
                                "data_windows_skipped": 0,
                                "resumed_from": None}

    if auto_resume:
        best = ckpt_io.latest_valid(ckpt_dir, quarantine_corrupt=True)
        # newest-COMMON-valid election: a host whose newest save is torn
        # drags every host down to the newest step ALL hosts can restore
        best = coord.elect_checkpoint(best)
        if best is not None:
            state, s = ckpt_io.load(ckpt_dir, template, step=best)
            if any_spiking:
                # a fresh segment starts calm: a crash mid-cooldown must
                # not pin the reduced LR forever
                state = dict(state)
                state["lr_scale"] = jnp.ones((), jnp.float32)
            counters["resumed_from"] = s
            pipeline.seek(s)
            log(f"run_loop: auto-resumed from {ckpt_dir} at step {s}")
    if (ckpt_dir and (ckpt_every or any_spiking)
            and ckpt_io.latest_valid(ckpt_dir) is None):
        # eager anchor save: rollback/resume always has a target, even
        # before the first ckpt_every boundary
        ckpt_io.save(ckpt_dir, int(state["step"]), state, keep=ckpt_keep,
                     n_shards=ckpt_shards)

    history = []
    times = collections.deque(maxlen=TELEMETRY_WINDOW)
    # one self-describing line so benchmark logs record which optimizer
    # backend (fused kernel vs jnp chain) produced the step times
    log(f"run_loop: opt_fused={opt_state_is_fused(state.get('opt'))} "
        f"backend={jax.default_backend()}")
    step_jit = jax.jit(train_step, donate_argnums=(0,))
    cur = int(state["step"])
    consec_skips = 0
    lr_scale_now = 1.0
    cooldown = 0
    # cumulative data-reorder offset: each reordered rollback adds the
    # width of the window it skipped, so later rollbacks keep skipping
    # FORWARD in the stream instead of landing back on poisoned batches
    data_lead = 0

    def do_rollback(origin: str, trigger: float, counter: str) -> None:
        """One coordinated rollback (DESIGN.md §12): elect the newest
        checkpoint every host can restore, agree on the (restore, seek)
        pair, then restore + seek + LR backoff.  Raises
        RollbackBudgetError past the shared budget."""
        nonlocal state, cur, lr_scale_now, cooldown, data_lead
        counters[counter] += 1
        total = counters["rollbacks"] + counters["eval_rollbacks"]
        if total > max_rollbacks:
            raise RollbackBudgetError(
                f"spike rollback budget ({max_rollbacks}) exhausted at "
                f"step {cur} ({origin} trigger={trigger:.4f})",
                {"step": cur, "loss": trigger, **counters})
        best = ckpt_io.latest_valid(ckpt_dir, quarantine_corrupt=True)
        best = coord.elect_checkpoint(best)
        if best is None:
            raise RollbackBudgetError(
                f"{origin} spike at step {cur} but no commonly-valid "
                f"checkpoint in {ckpt_dir} to roll back to",
                {"step": cur, "loss": trigger, **counters})
        restored, s = ckpt_io.load(ckpt_dir, template, step=best)
        if rollback_reorder and cur > s:
            # replay with DIFFERENT data: skip past the window [s, cur)
            # that fed the spike instead of re-feeding it at reduced LR
            data_lead += cur - s
            counters["data_windows_skipped"] += 1
        seek_to = s + data_lead
        # unanimity on the (restore, seek, origin) triple BEFORE mutating
        # anything: a host that would restore a different step or seek a
        # different index must abort loudly, not diverge silently
        coord.agree("rollback", (s, seek_to, origin))
        pipeline.seek(seek_to)
        cur = s
        lr_scale_now *= backoff_scale
        cooldown = cooldown_steps
        state = dict(restored)
        state["lr_scale"] = jnp.asarray(lr_scale_now, jnp.float32)
        if monitor is not None:
            monitor.reset()
        if eval_monitor is not None:
            eval_monitor.reset()
        log(f"run_loop: {origin} spike ({trigger:.4f}) — rolled back to "
            f"step {s} (seek {seek_to}, lead {data_lead}), "
            f"lr_scale={lr_scale_now:g} for {cooldown_steps} steps")

    while cur < n_steps:
        batch = next(pipeline)
        t0 = time.perf_counter()
        state, metrics = step_jit(state, batch)
        # the loss transfer doubles as the step sync; the guard flag
        # rides the same transfer
        loss_v = float(metrics["loss"])
        dt = time.perf_counter() - t0
        times.append(dt)
        cur += 1
        skipped = bool(metrics["skipped"]) if "skipped" in metrics else False

        if skipped:
            counters["skipped"] += 1
            consec_skips += 1
            if consec_skips > max_skips:
                diag = {"step": cur, "loss": loss_v,
                        "grad_norm": float(metrics["grad_norm"]),
                        **{k: v for k, v in counters.items()}}
                raise NonFiniteBudgetError(
                    f"{consec_skips} consecutive non-finite steps "
                    f"(budget {max_skips}) at step {cur}: loss={loss_v}, "
                    f"gnorm={diag['grad_norm']} — data or optimizer state "
                    f"is persistently poisoned", diag)
        else:
            consec_skips = 0
            if monitor is not None and monitor.observe(loss_v):
                do_rollback("loss", loss_v, "rollbacks")
                continue

        if cooldown > 0:
            cooldown -= 1
            if cooldown == 0 and lr_scale_now != 1.0:
                lr_scale_now = 1.0
                state = dict(state)
                state["lr_scale"] = jnp.ones((), jnp.float32)
                log(f"run_loop: cooldown over at step {cur}, lr restored")

        if step_hook is not None:
            step_hook(state, metrics)

        step = cur
        if log_every and step % log_every == 0:
            window = np.asarray(times)
            p50, p95 = (np.percentile(window, 50),
                        np.percentile(window, straggler_pct))
            log(f"step {step:6d} loss {loss_v:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"dt_p50 {p50*1e3:.1f}ms p95 {p95*1e3:.1f}ms "
                f"skipped {counters['skipped']} "
                f"rollbacks {counters['rollbacks']} "
                f"resumed_from {counters['resumed_from']}")
        if eval_every and eval_hook and step % eval_every == 0:
            ev = eval_hook(state)
            history.append((step, ev))
            if eval_monitor is not None:
                ev_scalar = _eval_scalar(ev)
                if ev_scalar is not None and eval_monitor.observe(ev_scalar):
                    do_rollback("eval", ev_scalar, "eval_rollbacks")
                    continue
        if ckpt_every and step % ckpt_every == 0:
            # never checkpoint while a spike is suspected: a hot monitor
            # means this state may be what we are about to roll away from
            hot = ((monitor is not None and monitor.hot)
                   or (eval_monitor is not None and eval_monitor.hot))
            if ckpt_dir and not hot:
                ckpt_io.save(ckpt_dir, step, state, keep=ckpt_keep,
                             n_shards=ckpt_shards)
            if ckpt_hook:
                ckpt_hook(state)
    return {"state": state, "history": history, "step_times": list(times),
            **counters}
