"""Distributed training launcher (the production entry point).

On a real TPU slice this process runs per host under
``jax.distributed.initialize()``; on the CPU container it drives the same
code on however many (forced) host devices exist.  The mesh, shardings,
step function and checkpoint path are identical to the dry-run's — the
dry-run IS this launcher minus execution.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
        --mesh 2x4 --steps 10 --batch 8 --seq 64 --smoke
"""

from __future__ import annotations

import argparse
import contextlib
import math
import os
import tempfile

# allow forcing host devices for local multi-device runs (must precede jax)
if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count="
        f"{os.environ['REPRO_FORCE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import QuantConfig, QuantPolicy  # noqa: E402
from repro.data import DataPipeline, lm_batch, permutation_table  # noqa: E402
from repro.distributed import state_shardings, train_batch_shardings  # noqa: E402
from repro.distributed.context import set_constraints  # noqa: E402
from repro.launch import specs as sp  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import mesh_from_arg  # noqa: E402
from repro.models.lm import lm_init  # noqa: E402
from repro.optim import adamw, cosine_with_warmup  # noqa: E402
from repro.train import (TrainConfig, init_state, make_optimizer,  # noqa: E402
                         make_train_step, run_loop)


def shard_setup(cfg, opt, mesh):
    """Train-state shardings on ``mesh`` for the chain ``opt``, with the
    activation constraints installed for the model's roles."""
    state_abs = jax.eval_shape(
        lambda k: init_state(lm_init(k, cfg), opt), jax.random.PRNGKey(0))
    state_sh = state_shardings(mesh, state_abs)
    set_constraints(residual=NamedSharding(mesh, P(("data",), None, "model")),
                    logits=NamedSharding(
                        mesh, P(("data",), None, None, "model")
                        if cfg.n_codebooks > 1 else P(("data",), None, "model")),
                    head_in=NamedSharding(mesh, P(("data",), None, None)))
    return state_sh


def init_sharded_state(cfg, opt, state_sh, seed: int = 0):
    """Initial train state (model init from ``seed``), placed per
    ``state_sh``."""
    return jax.jit(lambda k: init_state(lm_init(k, cfg), opt),
                   out_shardings=state_sh)(jax.random.PRNGKey(seed))


def make_batch_fn(cfg, batch: int, seq: int):
    """Step index -> the seeded synthetic LM batch of that step."""
    perm = permutation_table(0, cfg.vocab)

    def batch_fn(s):
        return lm_batch(0, s, batch, seq, cfg.vocab, perm,
                        n_codebooks=cfg.n_codebooks)
    return batch_fn


def make_pipeline(cfg, mesh, batch: int, seq: int):
    """Prefetching pipeline of ``make_batch_fn`` batches, data-sharded on
    ``mesh``."""
    batch_sh = train_batch_shardings(
        mesh, sp.train_batch_specs(cfg, batch, seq), batch)
    return DataPipeline(make_batch_fn(cfg, batch, seq), sharding=batch_sh,
                        prefetch=1)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--method", default="lotion")
    ap.add_argument("--lam", type=float, default=1000.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--placement", default=None,
                    choices=["loss", "decoupled"],
                    help="LOTION penalty placement (default: decoupled)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the training chaos harness (seeded faults "
                         "+ per-step invariant audit, train/faults.py) "
                         "instead of a plain run; exits nonzero on any "
                         "audit violation")
    ap.add_argument("--chaos-seed", type=int, default=1)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a profiler trace of the run into DIR "
                         "(README: Profiling)")
    args = ap.parse_args(argv)
    if args.chaos and args.microbatches != 1:
        ap.error("--chaos requires --microbatches 1 (the poison scalar "
                 "is per batch)")
    return args


def run(args):
    """Train as ``args`` says; returns ``run_loop``'s result (the chaos
    drive exits with its verdict instead)."""
    mesh = mesh_from_arg(args.mesh)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    qcfg = QuantConfig(method=args.method, fmt_name="int4", lam=args.lam,
                       policy=QuantPolicy(min_size=256 if args.smoke else 1024))
    tcfg = TrainConfig(quant=qcfg, n_microbatches=args.microbatches,
                       penalty_placement=args.placement)
    # the full update chain (clip -> [lotion] -> adamw core): one object
    # drives init_state, the sharding specs, and the step
    opt = make_optimizer(tcfg, adamw(cosine_with_warmup(args.lr, 5, args.steps)))

    state_sh = shard_setup(cfg, opt, mesh)

    # simulated host count: one host per data/pod-axis slice of the mesh
    # (the replica groups a real multi-host job would place one process
    # each on); "model" shards live inside a host.  1 on a model-only or
    # default mesh.  Drives both the chaos bus width and the checkpoint
    # shard count (one payload shard per host, as a real per-host
    # sharded save would write).
    n_hosts = 1
    for ax, n in mesh.shape.items():
        if ax != "model":
            n_hosts *= int(n)

    batch_fn = make_batch_fn(cfg, args.batch, args.seq)

    with mesh:
        if args.chaos:
            # chaos drive: fresh state per segment (the harness emulates
            # a supervisor restarting a killed job), loss carries the
            # poison seam, faults and audits run through public hooks
            from repro.train import faults as tfaults

            def make_state():
                return jax.jit(
                    lambda k: init_state(lm_init(k, cfg), opt,
                                         lr_scale=True),
                    out_shardings={**state_sh,
                                   "lr_scale": None})(jax.random.PRNGKey(0))

            step = make_train_step(
                cfg, tcfg, opt, grad_shardings=state_sh["params"],
                loss_fn=tfaults.chaos_loss_fn(cfg, tcfg))
            kw = {}
            if n_hosts > 1:
                # host-level tier: pick hook ordinals clear of the
                # seeded crash ordinals (a crash at the same ordinal
                # would end the segment before the kill's timeout is
                # ever observed).  The base plan is deterministic per
                # seed, so sampling it twice is free.
                base = tfaults.chaos_train_plan(args.chaos_seed,
                                                n_steps=args.steps)
                taken = set(base.crash_steps)
                free = (i for i in range(2, args.steps - 1)
                        if i not in taken)
                kill_at = next(free)
                straggle_at = next(free)
                kw = dict(n_hosts=n_hosts, host_kill_at=kill_at,
                          straggle_at=straggle_at,
                          corrupt_mode=("bitflip", n_hosts - 1),
                          torn_manifest_save=4,
                          # pin the spike burst late in the FETCH stream
                          # (ordinals run past n_steps because replays
                          # keep counting): it must land in the long
                          # final segment, past the monitor warmup, so
                          # the coordinated-rollback tier provably fires
                          spike_at=(5 * args.steps) // 4, spike_len=3)
            plan = tfaults.chaos_train_plan(args.chaos_seed,
                                            n_steps=args.steps, **kw)
            ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(
                prefix="chaos_train_")
            print(f"chaos: {plan.describe()} n_hosts={n_hosts} "
                  f"ckpt_dir={ckpt_dir}")
            summary = tfaults.run_chaos(
                step, make_state, batch_fn, plan, args.steps, ckpt_dir,
                n_hosts=n_hosts, ckpt_shards=n_hosts,
                log=print)
            counters = {k: summary[k] for k in
                        ("segments", "crashes", "resumes", "rollbacks",
                         "skipped", "replayed_steps", "saves",
                         "quarantined", "host_kill_timeouts",
                         "straggler_timeouts", "divergence_checks",
                         "data_windows_skipped")}
            print(f"chaos done: violations={len(summary['violations'])} "
                  f"{counters} final_loss={summary['final_loss']:.4f}")
            for v in summary["violations"]:
                print(f"  VIOLATION: {v}")
            ok = (not summary["violations"]
                  and summary["result"] is not None
                  and math.isfinite(summary["final_loss"]))
            if n_hosts > 1:
                # the distributed acceptance bar: every host-level fault
                # tier must have actually fired AND been healed
                ok = (ok and summary["host_kill_timeouts"] >= 1
                      and summary["straggler_timeouts"] >= 1
                      and summary["quarantined"] >= 1
                      and summary["rollbacks"] >= 1
                      and summary["divergence_checks"] >= 1)
            raise SystemExit(0 if ok else 1)

        params = init_sharded_state(cfg, opt, state_sh)
        step = make_train_step(cfg, tcfg, opt,
                               grad_shardings=state_sh["params"])
        pipe = make_pipeline(cfg, mesh, args.batch, args.seq)
        hooks = {}
        if args.ckpt_dir:
            # the loop's own atomic checkpointing + crash-exact restart
            # (DESIGN.md §11): re-running the same command after a kill
            # resumes from the newest CRC-verified checkpoint
            hooks = dict(ckpt_dir=args.ckpt_dir,
                         ckpt_every=max(args.steps // 2, 1),
                         ckpt_shards=n_hosts,
                         auto_resume=True)
        out = run_loop(step, params, pipe, args.steps, log_every=5, **hooks)
        print(f"done: {int(out['state']['step'])} steps on mesh "
              f"{dict(mesh.shape)} devices={mesh.size} "
              f"skipped={out['skipped']} rollbacks={out['rollbacks']} "
              f"resumed_from={out['resumed_from']}")
        pipe.close()
    return out


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        run(args)


if __name__ == "__main__":
    main()
