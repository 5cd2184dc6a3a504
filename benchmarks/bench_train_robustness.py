"""Training-robustness chaos replay: seeded faults + invariant audit +
fault-free bit parity (ISSUE 8 acceptance).

Three arms over the SAME tiny-LM train step (jnp chain — the CPU CI
backend; the fused kernel carries the identical SC_OK gate and is
covered by the tier-1 tests):

1. **plain** — ``run_loop`` with no checkpointing and no monitor: the
   ground-truth trajectory.
2. **fault-free chaos** — the full chaos harness (``run_chaos`` with
   ``plan=None``): auto-resume on, spike monitor armed, checkpoints
   written, poison scalar stamped 1.0 on every batch.  Its final
   ``params/opt/step`` must be BIT-IDENTICAL to the plain arm — the
   self-healing machinery is free when nothing goes wrong (multiply by
   1.0 and ``where(True, new, old)`` are IEEE identities).
3. **seeded chaos** — a :func:`repro.train.faults.chaos_train_plan`
   exercising every recovery tier: NaN/inf batches (skip), a sustained
   finite loss blow-up (spike rollback + LR backoff), hard kills after
   the step and mid-checkpoint-write (auto-resume), a bit-flipped
   published payload (quarantine).  The per-step
   :class:`~repro.train.faults.TrainAuditor` must report ZERO
   violations and the run must complete with a finite loss.

All columns are deterministic on a fixed backend (seeded plan, seeded
data, ``prefetch=0``), so ``check_regression.py`` gates them at zero
tolerance; the invariant/parity columns are the acceptance bar itself.

Emits ``BENCH_train.json`` (``--json-dir DIR``); ``--tiny`` is the CI
smoke (1-layer model, 18 steps) and is what the committed baseline was
generated from.
"""

from __future__ import annotations

import argparse
import os
import tempfile

# the distributed section needs forced host devices, and XLA reads the
# flag only before backend init — so the env dance happens at module
# top, before jax is imported.  Local runs therefore see the same
# 8-device backend CI generates the committed baselines on.
os.environ.setdefault("REPRO_FORCE_DEVICES", "8")
if ("--xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count="
        f"{os.environ['REPRO_FORCE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import QuantConfig, QuantPolicy
from repro.data import DataPipeline, lm_batch, permutation_table
from repro.launch.mesh import make_mesh
from repro.models.lm import LMConfig, lm_init
from repro.optim import adamw, constant
from repro.train import (TrainConfig, init_state, make_optimizer,
                         make_train_step)
from repro.train import faults as tfaults
from repro.train.loop import run_loop

from .common import emit, write_bench_json

# plan parameters verified (per mode) to exercise every recovery tier:
# >=1 skip, >=1 rollback, >=1 mid-write kill, >=1 quarantine, zero audit
# violations (see the committed baseline counters).  The quarantine tier
# needs the corrupted save to still be the newest candidate at some
# restore, so the corrupt ordinal shifts with the run length.
PLAN_TINY = dict(seed=1, spike_at=24, spike_len=3, n_crashes=1,
                 ckpt_crash_save=2, ckpt_crash_stage="manifest",
                 corrupt_save=3, corrupt_mode="bitflip")
PLAN_FULL = dict(seed=3, spike_at=24, spike_len=3, n_crashes=1,
                 ckpt_crash_save=2, ckpt_crash_stage="manifest",
                 corrupt_save=5, corrupt_mode="bitflip")
SPIKE_WARMUP = 4
CKPT_EVERY = 3

# host-level chaos plan for the distributed arm (DESIGN.md §12, ISSUE 9
# acceptance): one peer-host kill, one straggler past the deadline, one
# shard-targeted bitflip and one torn manifest on top of the data-level
# tiers.  Ordinals verified (like PLAN_TINY's) to make every tier
# actually fire on the deterministic CPU testbed.
DIST_MESH = (2, 4)                       # data x model -> n_hosts = 2
HOST_PLAN_TINY = dict(seed=1, spike_at=24, spike_len=3, n_crashes=1,
                      ckpt_crash_save=2, ckpt_crash_stage="manifest",
                      corrupt_save=3, corrupt_mode=("bitflip", 1),
                      torn_manifest_save=4,
                      n_hosts=2, host_kill_at=2, straggle_at=7)
HOST_PLAN_FULL = dict(seed=3, spike_at=24, spike_len=3, n_crashes=1,
                      ckpt_crash_save=2, ckpt_crash_stage="manifest",
                      corrupt_save=5, corrupt_mode=("bitflip", 1),
                      torn_manifest_save=6,
                      n_hosts=2, host_kill_at=2, straggle_at=7)


def _setup(tiny: bool):
    if tiny:
        cfg = LMConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=2, d_ff=64, vocab=32,
                       dtype=jnp.float32, remat=False)
        n_steps, b, l = 18, 4, 16
    else:
        cfg = LMConfig(name="small", n_layers=2, d_model=64, n_heads=4,
                       n_kv_heads=2, d_ff=128, vocab=64,
                       dtype=jnp.float32, remat=False)
        n_steps, b, l = 36, 8, 32
    tcfg = TrainConfig(
        quant=QuantConfig(method="lotion", fmt_name="int4", lam=1e3,
                          policy=QuantPolicy(min_size=64),
                          use_kernel=False),
        clip_norm=1.0, n_microbatches=1, seed=0)
    perm = permutation_table(0, cfg.vocab)

    def batch_fn(step):
        return lm_batch(0, step, b, l, cfg.vocab, perm)

    opt = make_optimizer(tcfg, adamw(constant(1e-2)))

    def make_state():
        params = lm_init(jax.random.PRNGKey(0), cfg)
        return init_state(params, opt, lr_scale=True)

    step = make_train_step(cfg, tcfg, opt,
                           loss_fn=tfaults.chaos_loss_fn(cfg, tcfg))
    plan_args = dict(PLAN_TINY if tiny else PLAN_FULL)
    host_plan_args = dict(HOST_PLAN_TINY if tiny else HOST_PLAN_FULL)
    config = {"arch": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                       "n_heads": cfg.n_heads, "vocab": cfg.vocab},
              "n_steps": n_steps, "batch": b, "seq": l,
              "plan": plan_args, "spike_warmup": SPIKE_WARMUP,
              "ckpt_every": CKPT_EVERY,
              "dist_mesh": list(DIST_MESH),
              "host_plan": {k: list(v) if isinstance(v, tuple) else v
                            for k, v in host_plan_args.items()}}
    return step, make_state, batch_fn, n_steps, plan_args, \
        host_plan_args, config


def _plain_run(step, make_state, batch_fn, n_steps):
    """Ground-truth trajectory: no checkpoints, no monitor, poison=1.0."""

    def fn(s):
        b = dict(batch_fn(s))
        b["poison"] = np.asarray(1.0, np.float32)
        return b

    pipe = DataPipeline(fn, prefetch=0)
    out = run_loop(step, make_state(), pipe, n_steps, log_every=0,
                   log=lambda *a, **k: None)
    pipe.close()
    return out["state"]


def _bit_parity(a, b) -> bool:
    """Bitwise equality of the params/opt/step slices of two states
    (``lr_scale`` and other driver-owned scalars are excluded — the
    plain arm never touches them)."""
    pa = {k: a[k] for k in ("params", "opt", "step")}
    pb = {k: b[k] for k in ("params", "opt", "step")}
    if (jax.tree_util.tree_structure(pa) != jax.tree_util.tree_structure(pb)):
        return False
    la = jax.tree_util.tree_leaves(pa)
    lb = jax.tree_util.tree_leaves(pb)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(la, lb))


def robustness(step, make_state, batch_fn, n_steps, plan_args) -> dict:
    plain = _plain_run(step, make_state, batch_fn, n_steps)

    with tempfile.TemporaryDirectory(prefix="bench_ff_") as d:
        ff = tfaults.run_chaos(step, make_state, batch_fn, None, n_steps, d,
                               ckpt_every=CKPT_EVERY,
                               spike_warmup=SPIKE_WARMUP)
    parity = ff["state"] is not None and _bit_parity(plain, ff["state"])

    plan = tfaults.chaos_train_plan(n_steps=n_steps, **plan_args)
    with tempfile.TemporaryDirectory(prefix="bench_chaos_") as d:
        ch = tfaults.run_chaos(step, make_state, batch_fn, plan, n_steps, d,
                               ckpt_every=CKPT_EVERY,
                               spike_warmup=SPIKE_WARMUP)

    return {
        "plan": plan.describe(),
        "invariant_violations": len(ch["violations"]),
        "violations": ch["violations"],
        "fault_free_violations": len(ff["violations"]),
        "fault_free_bit_parity": bool(parity),
        "chaos_completed": ch["result"] is not None,
        "final_loss_finite": bool(np.isfinite(ch["final_loss"])),
        "final_loss": float(ch["final_loss"]),
        "segments": ch["segments"],
        "crashes": ch["crashes"],
        "resumes": ch["resumes"],
        "rollbacks": ch["rollbacks"],
        "skipped_steps": ch["skipped"],
        "replayed_steps": ch["replayed_steps"],
        "steps_seen": ch["steps_seen"],
        "saves": ch["saves"],
        "corrupted_saves": ch["corrupted_saves"],
        "quarantined": ch["quarantined"],
    }


def distributed(step, make_state, batch_fn, n_steps,
                host_plan_args) -> dict:
    """The 2x4-mesh arm (DESIGN.md §12): plain mesh run, fault-free
    chaos bit parity on the SAME mesh, then the seeded host-level plan —
    peer-host kill, straggler, torn manifest, one corrupted shard of a
    2-shard save — with the cross-host fingerprint + replica audit on
    every step and zero tolerance for violations."""
    n_hosts = host_plan_args["n_hosts"]
    mesh = make_mesh(DIST_MESH, ("data", "model"))
    rep = NamedSharding(mesh, P())

    def make_state_mesh():
        return jax.tree.map(lambda x: jax.device_put(x, rep), make_state())

    probe = batch_fn(0)
    batch_sh = {k: NamedSharding(
        mesh, P(*(("data",) + (None,) * (np.asarray(v).ndim - 1))))
        for k, v in probe.items()}
    batch_sh["poison"] = rep             # injector-stamped scalar

    with mesh:
        def fn(s):
            b = dict(batch_fn(s))
            b["poison"] = np.asarray(1.0, np.float32)
            return b

        pipe = DataPipeline(fn, prefetch=0, sharding=batch_sh)
        plain = run_loop(step, make_state_mesh(), pipe, n_steps,
                         log_every=0, log=lambda *a, **k: None)["state"]
        pipe.close()

        with tempfile.TemporaryDirectory(prefix="bench_dist_ff_") as d:
            ff = tfaults.run_chaos(step, make_state_mesh, batch_fn, None,
                                   n_steps, d, ckpt_every=CKPT_EVERY,
                                   spike_warmup=SPIKE_WARMUP,
                                   n_hosts=n_hosts, ckpt_shards=n_hosts,
                                   batch_sharding=batch_sh)
        parity = ff["state"] is not None and _bit_parity(plain, ff["state"])

        plan = tfaults.chaos_train_plan(n_steps=n_steps, **host_plan_args)
        with tempfile.TemporaryDirectory(prefix="bench_dist_chaos_") as d:
            ch = tfaults.run_chaos(step, make_state_mesh, batch_fn, plan,
                                   n_steps, d, ckpt_every=CKPT_EVERY,
                                   spike_warmup=SPIKE_WARMUP,
                                   n_hosts=n_hosts, ckpt_shards=n_hosts,
                                   batch_sharding=batch_sh)

    return {
        "mesh": f"{DIST_MESH[0]}x{DIST_MESH[1]}",
        "n_hosts": n_hosts,
        "devices": int(mesh.size),
        "plan": plan.describe(),
        "invariant_violations": len(ch["violations"]),
        "violations": ch["violations"],
        "fault_free_violations": len(ff["violations"]),
        "fault_free_bit_parity": bool(parity),
        "chaos_completed": ch["result"] is not None,
        "final_loss_finite": bool(np.isfinite(ch["final_loss"])),
        "final_loss": float(ch["final_loss"]),
        "segments": ch["segments"],
        "crashes": ch["crashes"],
        "resumes": ch["resumes"],
        "rollbacks": ch["rollbacks"],
        "skipped_steps": ch["skipped"],
        "saves": ch["saves"],
        "quarantined": ch["quarantined"],
        "host_kills": ch["host_kills"],
        "straggles": ch["straggles"],
        "host_kill_timeouts": ch["host_kill_timeouts"],
        "straggler_timeouts": ch["straggler_timeouts"],
        "divergence_checks": ch["divergence_checks"],
        "data_windows_skipped": ch["data_windows_skipped"],
    }


def main(fast: bool = False, tiny: bool = False, json_dir: str = None):
    step, make_state, batch_fn, n_steps, plan_args, host_plan_args, \
        config = _setup(tiny or fast)
    rob = robustness(step, make_state, batch_fn, n_steps, plan_args)
    dist = distributed(step, make_state, batch_fn, n_steps,
                       host_plan_args)
    rec = {
        "bench": "train_robustness",
        "backend": jax.default_backend(),
        "config": config,
        "robustness": rob,
        "distributed": dist,
        "note": ("all counters are deterministic (seeded plan + seeded "
                 "data + prefetch=0 + virtual coordinator clock): "
                 "check_regression.py gates them at zero tolerance; "
                 "violations/parity are the acceptance bar itself"),
    }
    emit("train_chaos_violations", 0.0, f"n={rob['invariant_violations']}")
    emit("train_chaos_recovery", 0.0,
         f"skips={rob['skipped_steps']} rollbacks={rob['rollbacks']} "
         f"resumes={rob['resumes']} quarantined={rob['quarantined']}")
    emit("train_fault_free_parity", 0.0,
         f"bit_identical={rob['fault_free_bit_parity']}")
    emit("train_dist_chaos", 0.0,
         f"mesh={dist['mesh']} host_kills={dist['host_kill_timeouts']} "
         f"stragglers={dist['straggler_timeouts']} "
         f"quarantined={dist['quarantined']} "
         f"rollbacks={dist['rollbacks']} "
         f"violations={dist['invariant_violations']}")
    emit("train_dist_parity", 0.0,
         f"bit_identical={dist['fault_free_bit_parity']} "
         f"divergence_checks={dist['divergence_checks']}")

    # the acceptance bar holds regardless of baselines
    assert rob["invariant_violations"] == 0, rob["violations"]
    assert rob["fault_free_violations"] == 0
    assert rob["fault_free_bit_parity"], \
        "fault-free chaos replay diverged from the plain run"
    assert rob["chaos_completed"] and rob["final_loss_finite"]
    # the plan must actually exercise every recovery tier
    for tier in ("skipped_steps", "rollbacks", "resumes", "quarantined"):
        assert rob[tier] >= 1, f"chaos plan exercised no {tier}"
    # the distributed acceptance bar (ISSUE 9): zero violations, mesh
    # bit parity, and every host-level tier actually fired
    assert dist["invariant_violations"] == 0, dist["violations"]
    assert dist["fault_free_violations"] == 0
    assert dist["fault_free_bit_parity"], \
        "fault-free mesh chaos replay diverged from the plain 2x4 run"
    assert dist["chaos_completed"] and dist["final_loss_finite"]
    for tier in ("host_kill_timeouts", "straggler_timeouts",
                 "quarantined", "rollbacks", "divergence_checks"):
        assert dist[tier] >= 1, f"distributed chaos exercised no {tier}"

    if json_dir is not None:
        print(f"wrote {write_bench_json('train', rec, json_dir)}")
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 1-layer model, 18 chaos steps")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_train.json into this directory")
    a = ap.parse_args()
    main(fast=a.fast, tiny=a.tiny, json_dir=a.json_dir)
