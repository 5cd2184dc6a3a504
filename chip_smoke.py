#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, through the entry points a
user calls.

* Train phase: granite-3-2b at its published widths, depth cut to
  ``TRAIN_LAYERS``, trained with LOTION (int4, decoupled placement) by the
  fused LOTION-AdamW Pallas step: ``make_optimizer`` / ``init_state`` /
  ``make_train_step`` / ``run_loop``.  One fused update is also checked
  against the unfused ``use_kernel=False`` chain on identical inputs.
* Serve phase: the full 40-layer granite-3-2b with ``rtn:int4`` QTensor
  weights (``quantize_params``), served by the continuous-batching
  ``Scheduler`` with paged int4 KV, chunked prefill and the prefix cache.
  Prefill and first-decode logits of the kernel path are checked against
  the jnp path (``qtensor_use_kernel(False)``).

    python3 chip_smoke.py            # one chip: train phase, then serve
    python3 chip_smoke.py --chips 4  # only: the train step on a 2x2 mesh,
                                     # against the same steps on one device

Weights are random, made from a seed; training data is the repository's
seeded synthetic Markov stream.  Earlier lines print layer counts, phase
and compile seconds and peak device memory: a first reading, not a
benchmark.  The last line, printed only when every check passed, is one
JSON object ``{"ok": true, "device": {...}}``.  The script exits nonzero
without it when the first device is not a TPU.  Everything runs in this
one process (the chip belongs to one process at a time).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (QuantConfig, QuantPolicy, get_format,  # noqa: E402
                        quantize_params, qtensor_use_kernel)
from repro.distributed.context import clear_constraints  # noqa: E402
from repro.kernels import compiled_kernels  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import (init_sharded_state, make_batch_fn,  # noqa: E402
                                make_pipeline, shard_setup)
from repro.models.lm import (cache_insert_paged,  # noqa: E402
                             init_paged_pool, lm_decode, lm_init, lm_prefill)
from repro.optim import adamw, apply_updates, cosine_with_warmup  # noqa: E402
from repro.serve import Scheduler, SchedulerConfig, ServeConfig  # noqa: E402
from repro.serve.slots import COMPLETED  # noqa: E402
from repro.train import (TrainConfig, make_optimizer,  # noqa: E402
                         make_train_step, run_loop)
from repro.train.loop import (_link_metrics, make_loss_fn,  # noqa: E402
                              opt_state_is_fused)

ARCH = "granite-3-2b"
SEED = 0

# ---- train phase ----------------------------------------------------------
# Every width is published; only depth is cut.  fp32 weights + gradients +
# AdamW moments are ~16 bytes/param: ~61M params per layer plus the 101M
# tied embedding.  The v5e compiler fits the whole step at batch 8 x 2048
# into its 15.75 GB of HBM with 10 layers; with 11 it needs 16.13 GB
# (8.60 GB of arguments, 7.53 GB of program).  The train phase runs first,
# on an empty device.
TRAIN_LAYERS = 10
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 4
ATTN_CHUNK = LOGIT_CHUNK = 512      # streaming attention / chunked CE head
LAM, LR = 1000.0, 1e-3
# the fused-vs-chain check holds two optimizer states and two gradient
# trees at once, so it runs at full width on the first 2 layers
CMP_LAYERS = 2
# Fused step vs unfused chain on IDENTICAL params, gradients and state: both
# evaluate the same fp32 elementwise rule, so they differ only by the
# rounding of division/sqrt (a few ulp, ~1e-6 relative) and at rare
# quantization-grid knife edges.  1e-3 leaves ~1000x headroom over that
# while a wrong bias correction, clip scale or LOTION term moves the
# relative L2 of the update (or of the penalty) by 1e-2 or more.
OPT_TOL = 1e-3

# ---- serve phase ----------------------------------------------------------
N_SLOTS, CHUNK, NEW_TOKENS = 8, 256, 64
PROMPT_LENS = (2048, 1536, 1024, 768, 512, 1280)
SHARED_PREFIX = 512          # requests 0..2 share their first 2 chunks
# Kernel vs jnp logits (relative L2 over the vocab rows).  Activations are
# fp32, but at default precision the TPU runs fp32 matmuls, XLA's and
# Mosaic's alike, as one MXU pass on bf16-rounded operands (unit roundoff
# 2^-8 ~ 3.9e-3 per operand), compounded over 40 random-init layers --
# about sqrt(40) * 3.9e-3 ~ 2.5e-2 for each path, in a different order.
# Two such paths may differ by about twice that; a kernel bug (a wrong
# nibble, scale or mask) moves the logits by O(1) relative.
LOGITS_TOL = 5e-2
# Both paths are also held against the jnp path at HIGHEST matmul
# precision (the fp32 truth for these int4 weights and this int4 KV): the
# kernel path must be no less accurate than the jnp path, within 1.5x,
# plus 1e-4 for fp32 accumulation order where the jnp path is itself
# fp32-exact (as on a CPU).
REF_FACTOR, REF_FLOOR = 1.5, 1e-4

# ---- --chips 4 ------------------------------------------------------------
SHARDED_STEPS = 3
# Per-step loss, 2x2 mesh vs one device: the sharded program reduces
# matmul partials over the model axis and the loss over the data axis in
# another order, in bf16 activations with fp32 accumulation.  The loss is a
# mean over 16k tokens, so those roundings average down far below 2^-8;
# 5e-3 relative still catches a mis-sharded step (a dropped shard or a
# double-counted batch half moves the loss by O(1e-1)).
SHARDED_LOSS_TOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device=None) -> int:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def tree_rel_l2(a, b) -> float:
    num = sum(float(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32))
                            ** 2)) for x, y in zip(jax.tree.leaves(a),
                                                   jax.tree.leaves(b)))
    den = sum(float(jnp.sum(y.astype(jnp.float32) ** 2))
              for y in jax.tree.leaves(b))
    return (num / max(den, 1e-30)) ** 0.5


def compile_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def program_bytes(compiled) -> dict:
    """The compiler's own account of a program's device memory."""
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, f"{k}_size_in_bytes", -1))
            for k in ("argument", "output", "alias", "temp")} if ma else {}


class Checks:
    """Named pass/fail records; every failure is printed as it happens.

    ``require_kernels=False`` skips the compiled-kernel checks: only the
    TPU backend compiles Pallas kernels (elsewhere they run in interpret
    mode and leave no ``tpu_custom_call``)."""

    def __init__(self, require_kernels: bool = True):
        self.require_kernels = require_kernels
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"check {'PASS' if ok else 'FAIL'} {name} {detail}".rstrip())
        if not ok:
            self.failed.append(name)
        return ok

    def kernels(self, name: str, found, *required: str) -> bool:
        """Every kernel in ``required`` is compiled into the program whose
        ``compiled_kernels`` count is ``found``."""
        if not self.require_kernels:
            return True
        return self(name, all(found.get(k, 0) > 0 for k in required),
                    f"need {list(required)} found {dict(found)}")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def train_config(lam: float = LAM, use_kernel: bool = True,
                 attn_chunk: int = ATTN_CHUNK,
                 logit_chunk: int = LOGIT_CHUNK) -> TrainConfig:
    """LOTION int4, decoupled placement; ``use_kernel`` pins the fused
    Pallas step (True) or the unfused jnp chain (False)."""
    q = QuantConfig(method="lotion", fmt_name="int4", lam=lam,
                    use_kernel=use_kernel, policy=QuantPolicy(min_size=1024),
                    penalty_placement="decoupled")
    return TrainConfig(quant=q, attn_chunk=attn_chunk,
                       logit_chunk=logit_chunk)


def train_phase(cfg, check: Checks, *, batch: int, seq: int, steps: int,
                tcfg: TrainConfig, mesh=None) -> dict:
    """A few LOTION steps through the launcher's setup and ``run_loop``."""
    mesh = mesh if mesh is not None else make_mesh((1, 1), ("data", "model"))
    opt = make_optimizer(tcfg, adamw(cosine_with_warmup(LR, 2, steps)))
    t0 = time.perf_counter()
    with mesh:
        state_sh = shard_setup(cfg, opt, mesh)
        state = init_sharded_state(cfg, opt, state_sh, seed=SEED)
        step = make_train_step(cfg, tcfg, opt,
                               grad_shardings=state_sh["params"])
        pipe = make_pipeline(cfg, mesh, batch, seq)
        t_init = time.perf_counter() - t0
        fused = opt_state_is_fused(state["opt"])
        # compile the step run_loop will run (same function, same
        # donation, same placed batch), then rewind the pipeline
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, next(pipe)).compile()
        t_compile = time.perf_counter() - t0
        hlo, step_bytes = compiled.as_text(), program_bytes(compiled)
        pipe.seek(0)
        losses = []

        def on_step(_state, metrics):
            losses.append(float(metrics["loss"]))

        t0 = time.perf_counter()
        out = run_loop(step, state, pipe, steps, log_every=1, log=log,
                       step_hook=on_step)
        t_loop = time.perf_counter() - t0
        pipe.close()
    clear_constraints()
    kernels = compiled_kernels(hlo)
    res = dict(layers=cfg.n_layers, losses=losses, skipped=out["skipped"],
               rollbacks=out["rollbacks"], opt_fused=fused,
               kernels=dict(kernels), init_s=t_init, compile_s=t_compile,
               step_bytes=step_bytes, loop_s=t_loop,
               step_s=list(out["step_times"]),
               state=out["state"])
    check("train.opt_fused", fused == tcfg.quant.kernel_enabled,
          f"opt_fused={fused}")
    if fused:
        check.kernels("train.step_kernels", kernels, "opt_step")
    check("train.losses_finite",
          len(losses) == steps and all(np.isfinite(losses)), f"{losses}")
    check("train.no_skips_or_rollbacks",
          out["skipped"] == 0 and out["rollbacks"] == 0,
          f"skipped={out['skipped']} rollbacks={out['rollbacks']}")
    return res


def two_updates(opt):
    """Jitted (params, g1, g2) -> (params after two updates of ``opt``
    from a fresh state, the LOTION penalty of the second)."""
    @jax.jit
    def run(params, g1, g2):
        st = opt.init(params)
        p = params
        for g in (g1, g2):
            upd, st = opt.update(g, st, p, fisher=opt.fisher(st))
            p = upd if opt.applies_updates else apply_updates(p, upd)
        return p, _link_metrics(st)["penalty"]
    return run


def opt_compare(cfg, check: Checks, *, batch: int, seq: int,
                lam: float = LAM) -> dict:
    """Two updates of the fused LOTION-AdamW step against the unfused
    ``use_kernel=False`` chain, from the same params, with the same two
    gradient trees (the second update sees a nonzero Fisher, so the LOTION
    term is live)."""
    tk, tr = train_config(lam, True), train_config(lam, False)
    sched = cosine_with_warmup(LR, 2, 10)
    opt_k = make_optimizer(tk, adamw(sched))
    opt_r = make_optimizer(tr, adamw(sched))
    params = jax.jit(lambda k: lm_init(k, cfg))(jax.random.PRNGKey(SEED))
    batch_fn = make_batch_fn(cfg, batch, seq)
    loss_fn = make_loss_fn(cfg, tk)
    grad_fn = jax.jit(jax.grad(lambda p, b: loss_fn(
        p, b, jax.tree.map(jnp.zeros_like, p), jax.random.PRNGKey(0))[0]))
    grads = [grad_fn(params, batch_fn(i)) for i in range(2)]

    run_k = two_updates(opt_k)
    hlo = compile_text(run_k, params, *grads)
    p_k, pen_k = run_k(params, *grads)
    p_r, pen_r = two_updates(opt_r)(params, *grads)
    d_k = jax.tree.map(jnp.subtract, p_k, params)
    d_r = jax.tree.map(jnp.subtract, p_r, params)
    upd_err = tree_rel_l2(d_k, d_r)
    pen_err = abs(float(pen_k) - float(pen_r)) / max(abs(float(pen_r)),
                                                     1e-30)
    kernels = compiled_kernels(hlo)
    check.kernels("train.compare_kernels", kernels, "opt_step")
    check("train.fused_vs_chain_update", upd_err <= OPT_TOL,
          f"rel_l2={upd_err:.3e} tol={OPT_TOL}")
    check("train.fused_vs_chain_penalty",
          pen_err <= OPT_TOL and float(pen_r) > 0,
          f"fused={float(pen_k):.6e} chain={float(pen_r):.6e} "
          f"rel={pen_err:.3e} tol={OPT_TOL}")
    return dict(layers=cfg.n_layers, update_rel_l2=upd_err,
                penalty_rel=pen_err, kernels=dict(kernels))


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def serving_params(cfg, seed: int = SEED):
    """Random granite weights from ``seed`` in ``rtn:int4`` QTensor storage
    (the serve launcher's ``quantize_params`` call), built in one program
    so the fp32 tree never sits whole beside its codes."""
    fmt = get_format("int4")
    policy = QuantPolicy(min_size=1024)
    return jax.jit(lambda k: quantize_params(lm_init(k, cfg), fmt, policy,
                                             -1, mode="rtn"))(
        jax.random.PRNGKey(seed))


def make_prompts(vocab: int, lens, shared: int, seed: int = SEED):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=shared).tolist()
    out = []
    for i, n in enumerate(lens):
        body = rng.integers(1, vocab, size=n).tolist()
        out.append(prefix + body[shared:] if i < 3 else body)
    return out


def serve_phase(cfg, params, check: Checks, *, n_slots: int, chunk: int,
                prompt_lens, shared: int, new_tokens: int) -> dict:
    """Requests through ``Scheduler.submit``/``step`` with paged int4 KV,
    chunked prefill (``block_size == prefill_chunk``) and the prefix
    cache; the Pallas kernels are pinned on (``use_kernel=True``)."""
    cache_len = -(-(max(prompt_lens) + new_tokens) // chunk) * chunk
    scfg = ServeConfig(weights="fp32", use_kernel=True, kv_quant="int4",
                       max_new_tokens=new_tokens)
    sch = Scheduler(cfg, params, scfg, SchedulerConfig(
        n_slots=n_slots, cache_len=cache_len, prefill_chunk=chunk,
        prefix_cache=True, prefix_cache_blocks=16, paged=True,
        block_size=chunk))
    prompts = make_prompts(cfg.vocab, prompt_lens, shared)
    t0 = time.perf_counter()
    rids = [sch.submit(p, new_tokens) for p in prompts]
    n_steps = 0
    while sch.has_work():
        sch.step()
        n_steps += 1
    t_run = time.perf_counter() - t0
    reqs = [sch.requests[r] for r in rids]
    t0 = time.perf_counter()
    tick_hlo = compile_text(sch._tick, sch.params, sch._pool_cache,
                            sch._tables, sch._state, sch._tick_key)
    chunk_hlo = compile_text(
        sch._chunk, sch.params, sch._fresh_row(),
        jnp.zeros((1, chunk), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), sch._key)
    t_hlo = time.perf_counter() - t0
    tick_k, chunk_k = compiled_kernels(tick_hlo), compiled_kernels(chunk_hlo)
    counters = dict(sch.counters)
    check("serve.all_completed",
          all(r.state == COMPLETED and len(r.out) == new_tokens
              for r in reqs),
          f"states={[r.state for r in reqs]} "
          f"lens={[len(r.out) for r in reqs]}")
    check("serve.no_nan_fallback",
          counters["nan_events"] == 0 and counters["nan_retries"] == 0
          and counters["failed"] == 0,
          f"nan_events={counters['nan_events']} "
          f"nan_retries={counters['nan_retries']} "
          f"failed={counters['failed']}")
    check("serve.prefix_shared", sch.prefill_tokens_skipped > 0,
          f"prefill_tokens_skipped={sch.prefill_tokens_skipped} "
          f"blocks_shared={sch.prefix_blocks_shared}")
    check.kernels("serve.tick_kernels", tick_k, "wqt_matmul",
                  "decode_attn_paged")
    check.kernels("serve.prefill_kernels", chunk_k, "wqt_matmul")
    return dict(layers=cfg.n_layers, cache_len=cache_len, steps=n_steps,
                run_s=t_run, recompile_for_hlo_s=t_hlo, ticks=sch.n_ticks,
                prefill_chunks=sch.n_prefills, counters=counters,
                tokens=sum(len(r.out) for r in reqs),
                prefill_tokens=sch.prefill_tokens_computed,
                prefill_tokens_skipped=sch.prefill_tokens_skipped,
                tick_kernels=dict(tick_k), prefill_kernels=dict(chunk_k))


def logits_compare(cfg, params, check: Checks, *, batch: int,
                   prompt_len: int, block_size: int) -> dict:
    """Prefill and first paged-decode logits, kernel path vs the jnp path,
    on the same int4 weights and the same int4 KV pool; both also against
    the jnp path at HIGHEST matmul precision."""
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 3),
                              (batch, prompt_len), 1, cfg.vocab)
    cache_len = prompt_len + block_size
    bps = cache_len // block_size
    tables = 1 + jnp.arange(batch * bps, dtype=jnp.int32).reshape(batch, bps)
    pos = jnp.full((batch,), prompt_len, jnp.int32)

    def prefill(use_kernel, precision=None):
        @jax.jit
        def f(p, t):
            with qtensor_use_kernel(use_kernel), \
                    jax.default_matmul_precision(precision):
                return lm_prefill(p, cfg, t, cache_len=cache_len,
                                  kv_quant="int4")
        return f

    def decode(use_kernel, precision=None):
        @jax.jit
        def f(p, pool, tok):
            with qtensor_use_kernel(use_kernel), \
                    jax.default_matmul_precision(precision):
                return lm_decode(p, cfg, pool, tok, pos,
                                 block_tables=tables)
        return f

    pre_k, pre_r = prefill(True), prefill(False)
    logits_k, cache = pre_k(params, toks)
    logits_r, _ = pre_r(params, toks)
    logits_x, _ = prefill(False, "highest")(params, toks)
    pool = init_paged_pool(cfg, 1 + batch * bps, block_size,
                           dtype=cfg.dtype, kv_quant="int4")
    write = jnp.ones((bps,), bool)
    for i in range(batch):
        row = jax.tree.map(lambda x: x[:, i:i + 1], cache)
        pool = cache_insert_paged(pool, row, tables[i], write)
    tok = jnp.argmax(logits_k[:, 0], -1).astype(jnp.int32)[:, None]
    dec_k, dec_r = decode(True), decode(False)
    dl_k, _ = dec_k(params, pool, tok)
    dl_r, _ = dec_r(params, pool, tok)
    dl_x, _ = decode(False, "highest")(params, pool, tok)
    pre_kern = compiled_kernels(compile_text(pre_k, params, toks))
    dec_kern = compiled_kernels(compile_text(dec_k, params, pool, tok))
    res = {}
    for name, k, r, x, kern in (
            ("prefill", logits_k, logits_r, logits_x, pre_kern),
            ("decode", dl_k, dl_r, dl_x, dec_kern)):
        err = rel_l2(k[:, 0], r[:, 0])
        k_ref, r_ref = rel_l2(k[:, 0], x[:, 0]), rel_l2(r[:, 0], x[:, 0])
        check(f"serve.{name}_logits_kernel_vs_jnp", err <= LOGITS_TOL,
              f"rel_l2={err:.3e} tol={LOGITS_TOL} kernels={dict(kern)}")
        check(f"serve.{name}_logits_vs_highest",
              k_ref <= REF_FACTOR * r_ref + REF_FLOOR,
              f"kernel={k_ref:.3e} jnp={r_ref:.3e} factor={REF_FACTOR} "
              f"floor={REF_FLOOR}")
        res.update({f"{name}_rel_l2": err, f"{name}_kernel_vs_highest": k_ref,
                    f"{name}_jnp_vs_highest": r_ref})
    check.kernels("serve.compare_prefill_kernels", pre_kern, "wqt_matmul")
    check.kernels("serve.compare_decode_kernels", dec_kern, "wqt_matmul",
                  "decode_attn_paged")
    return res


# --------------------------------------------------------------------------
# --chips 4: the sharded train step
# --------------------------------------------------------------------------

def state_bytes_per_device(state) -> dict:
    out = {}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            d = shard.device.id
            out[d] = out.get(d, 0) + shard.data.nbytes
    return out


def sharded_phase(cfg, check: Checks, *, batch: int, seq: int,
                  steps: int) -> dict:
    """The same LOTION steps on a 2x2 (data x model) mesh and on one of
    its devices; losses must agree and the 2x2 state must be spread over
    all four devices."""
    devs = jax.devices()[:4]
    tcfg = train_config()
    runs = {}
    # the unsharded run first, while device 0 is empty
    for name, mesh in (("one_device", make_mesh((1, 1), ("data", "model"),
                                                devices=devs[:1])),
                       ("mesh2x2", make_mesh((2, 2), ("data", "model"),
                                             devices=devs))):
        t0 = time.perf_counter()
        res = train_phase(cfg, check, batch=batch, seq=seq, steps=steps,
                          tcfg=tcfg, mesh=mesh)
        per_dev = state_bytes_per_device(res.pop("state"))
        res.update(wall_s=time.perf_counter() - t0, state_bytes=per_dev,
                   peak_bytes={d.id: peak_bytes(d) for d in devs})
        runs[name] = res
        log(f"{name}: losses={res['losses']} kernels={res['kernels']} "
            f"state_bytes_per_device={per_dev} "
            f"peak_bytes={res['peak_bytes']} compile_s={res['compile_s']:.1f}")
    la = np.asarray(runs["mesh2x2"]["losses"])
    lb = np.asarray(runs["one_device"]["losses"])
    err = float(np.max(np.abs(la - lb) / np.abs(lb)))
    check("chips4.losses_agree", err <= SHARDED_LOSS_TOL,
          f"max_rel={err:.3e} tol={SHARDED_LOSS_TOL}")
    sh = runs["mesh2x2"]["state_bytes"]
    total = sum(runs["one_device"]["state_bytes"].values())
    check("chips4.state_sharded",
          len(sh) == 4 and max(sh.values()) <= 0.5 * total
          and min(sh.values()) >= 0.1 * total,
          f"per_device={sh} single_device_total={total}")
    return dict(loss_max_rel=err, **{k: {kk: vv for kk, vv in v.items()
                                         if kk != "step_s"}
                                     for k, v in runs.items()})


# --------------------------------------------------------------------------

def device_record() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 "
                         "mesh against one device")
    args = ap.parse_args(argv)
    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev}); refusing",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {dev['count']} "
              f"device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev} compile cache: {enable_compile_cache()}")
    check = Checks()
    base = get_config(ARCH)
    cfg = dataclasses.replace(base, n_layers=TRAIN_LAYERS)

    if args.chips == 4:
        log(f"sharded train: {ARCH} layers={cfg.n_layers} "
            f"batch={TRAIN_BATCH} seq={TRAIN_SEQ} steps={SHARDED_STEPS}")
        rec = sharded_phase(cfg, check, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                            steps=SHARDED_STEPS)
        log(f"sharded result: {json.dumps(rec, default=str)}")
    else:
        log(f"train: {ARCH} layers={cfg.n_layers} (of {base.n_layers}) "
            f"batch={TRAIN_BATCH} seq={TRAIN_SEQ} steps={TRAIN_STEPS}")
        tr = train_phase(cfg, check, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                         steps=TRAIN_STEPS, tcfg=train_config())
        del tr["state"]
        log(f"train: losses={tr['losses']} kernels={tr['kernels']} "
            f"init_s={tr['init_s']:.1f} compile_s={tr['compile_s']:.1f} "
            f"loop_s={tr['loop_s']:.1f} step_s={tr['step_s']} "
            f"step_program_bytes={tr['step_bytes']} "
            f"peak_bytes_in_use={peak_bytes()}")

        t0 = time.perf_counter()
        cmp_cfg = dataclasses.replace(base, n_layers=CMP_LAYERS)
        cmp = opt_compare(cmp_cfg, check, batch=TRAIN_BATCH, seq=TRAIN_SEQ)
        log(f"fused-vs-chain ({CMP_LAYERS} layers): {cmp} "
            f"{time.perf_counter() - t0:.1f}s")

        t0 = time.perf_counter()
        params = serving_params(base)
        jax.block_until_ready(params)
        log(f"serve: {ARCH} layers={base.n_layers} rtn:int4 QTensor params "
            f"in {time.perf_counter() - t0:.1f}s")
        sv = serve_phase(base, params, check, n_slots=N_SLOTS, chunk=CHUNK,
                         prompt_lens=PROMPT_LENS, shared=SHARED_PREFIX,
                         new_tokens=NEW_TOKENS)
        log(f"serve: {json.dumps(sv)} peak_bytes_in_use={peak_bytes()}")
        lc = logits_compare(base, params, check, batch=2, prompt_len=512,
                            block_size=CHUNK)
        log(f"logits: {lc} peak_bytes_in_use={peak_bytes()}")

    if check.failed:
        log(f"FAILED checks: {check.failed}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
