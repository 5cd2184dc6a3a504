"""Distributed self-healing tests (DESIGN.md §12): coordinator agreement
rounds (election, unanimity, barrier, timeout), divergence audit,
sharded checkpoint trust (one bad shard untrusts the whole step),
fsync/write-stage ordering, the per-example cross-shard skip gate, the
eval-CE spike monitor, data-reordering rollbacks, and — with
``REPRO_FORCE_DEVICES=8`` — mesh-level skip agreement and elastic
restore across mesh shapes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import io as ckpt_io
from repro.core import QuantConfig, QuantPolicy
from repro.data import DataPipeline, lm_batch, permutation_table
from repro.distributed import (DEAD, AgreementError, Coordinator,
                               CoordinatorTimeout, InProcessBus, Straggle,
                               replica_divergence, tree_fingerprint)
from repro.launch.mesh import make_mesh
from repro.models.lm import LMConfig, lm_init
from repro.optim import adamw, constant
from repro.train import (InjectedCrash, TrainConfig, init_state,
                         make_optimizer, make_train_step)
from repro.train import faults as tfaults
from repro.train.loop import make_loss_fn, run_loop

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs REPRO_FORCE_DEVICES=8 forced host devices")

CFG = LMConfig(name="dr", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
               d_ff=64, vocab=32, dtype=jnp.float32, remat=False)
PERM = permutation_table(0, CFG.vocab)
_QUIET = {"log_every": 0, "log": lambda *a, **k: None}


def _batch(step, poison=1.0):
    b = dict(lm_batch(0, step, 4, 16, CFG.vocab, PERM))
    b["poison"] = np.asarray(poison, np.float32)
    return b


def _tcfg(use_kernel=False):
    return TrainConfig(
        quant=QuantConfig(method="lotion", fmt_name="int4", lam=1e3,
                          policy=QuantPolicy(min_size=64),
                          use_kernel=use_kernel),
        clip_norm=1.0)


def _build(use_kernel=False, loss_fn=None):
    tcfg = _tcfg(use_kernel)
    opt = make_optimizer(tcfg, adamw(constant(1e-2)))
    step = make_train_step(CFG, tcfg, opt,
                           loss_fn=loss_fn
                           or tfaults.chaos_loss_fn(CFG, tcfg))
    state = init_state(lm_init(jax.random.PRNGKey(0), CFG), opt)
    return step, state


def _bits_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(la, lb))


# -------------------------------------------------------------- coordinator

def test_single_host_rounds_are_trivially_unanimous():
    c = Coordinator()
    assert c.n_hosts == 1
    assert c.elect_checkpoint(7) == 7
    assert c.elect_checkpoint(None) is None
    assert c.agree("rollback", (3, 5, "loss")) == (3, 5, "loss")
    c.barrier("x")
    assert c.check_fingerprint(1, "abcd1234") == []
    assert c.rounds == 5


def test_elect_checkpoint_takes_min_over_hosts():
    # host 2's newest valid save is step 3 — everyone restores step 3
    bus = InProcessBus(3, peer_fn=lambda h, k, v: 3 if h == 2 else v)
    c = Coordinator(bus)
    assert c.elect_checkpoint(9) == 3


def test_elect_checkpoint_none_if_any_host_has_none():
    bus = InProcessBus(2, peer_fn=lambda h, k, v: None)
    assert Coordinator(bus).elect_checkpoint(9) is None


def test_agree_mismatch_is_typed_error_with_votes():
    bus = InProcessBus(2, peer_fn=lambda h, k, v: ("other",))
    with pytest.raises(AgreementError) as ei:
        Coordinator(bus).agree("seek", ("mine",))
    assert ei.value.votes[1] == ("other",)


def test_dead_host_converts_to_timeout_not_hang():
    bus = InProcessBus(4)
    bus.kill(2)
    c = Coordinator(bus)
    with pytest.raises(CoordinatorTimeout) as ei:
        c.elect_checkpoint(5)
    assert ei.value.missing == (2,)
    # a peer_fn returning DEAD behaves identically
    bus2 = InProcessBus(2, peer_fn=lambda h, k, v: DEAD)
    with pytest.raises(CoordinatorTimeout):
        Coordinator(bus2).barrier()


def test_straggler_past_deadline_is_dead_under_it_is_fine():
    bus = InProcessBus(2)
    bus.straggle(1, 5.0)
    c = Coordinator(bus, timeout=30.0)
    c.barrier()                          # 5s < 30s: answers in time
    bus.straggle(1, 120.0)
    with pytest.raises(CoordinatorTimeout) as ei:
        c.barrier()
    assert ei.value.missing == (1,)
    # a Straggle returned by the peer_fn max-merges with bus state
    bus3 = InProcessBus(2, peer_fn=lambda h, k, v: Straggle(99.0))
    with pytest.raises(CoordinatorTimeout):
        Coordinator(bus3, timeout=30.0).barrier()


def test_heal_all_models_host_replacement():
    bus = InProcessBus(3)
    bus.kill(1)
    bus.straggle(2, 1e9)
    c = Coordinator(bus)
    with pytest.raises(CoordinatorTimeout):
        c.barrier()
    bus.heal_all()
    c.barrier()


def test_fingerprint_divergence_named_per_host():
    bus = InProcessBus(3, peer_fn=lambda h, k, v: "bad0bad0" if h == 2
                       else v)
    out = Coordinator(bus).check_fingerprint(11, "aaaa0000")
    assert len(out) == 1 and "host 2" in out[0] and "step 11" in out[0]


def test_driver_host_cannot_be_killed_through_bus():
    bus = InProcessBus(2)
    with pytest.raises(ValueError):
        bus.kill(0)
    with pytest.raises(ValueError):
        bus.straggle(5, 1.0)             # no such host either


# ------------------------------------------------------------------- audit

def test_tree_fingerprint_is_deterministic_and_sensitive():
    t = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
         "b": {"c": np.ones((4,), np.int32)}}
    d1 = tree_fingerprint(t)
    assert d1 == tree_fingerprint(jax.tree.map(np.copy, t))
    t2 = jax.tree.map(np.copy, t)
    t2["b"]["c"][1] = 2
    assert tree_fingerprint(t2) != d1
    # dtype is part of the identity, not just the bytes
    t3 = {"a": t["a"], "b": {"c": t["b"]["c"].view(np.uint32)}}
    assert tree_fingerprint(t3) != d1


# ------------------------------------------------- sharded checkpoint trust

def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {"w1": r.normal(size=(16, 8)).astype(np.float32),
            "w2": r.normal(size=(8, 8)).astype(np.float32),
            "b": r.normal(size=(8,)).astype(np.float32),
            "step": jnp.asarray(0, jnp.int32)}


def test_sharded_save_layout_and_roundtrip(tmp_path):
    tree = _tree()
    ckpt_io.save(str(tmp_path), 5, tree, n_shards=3)
    d = tmp_path / "step_0000000005"
    names = sorted(os.listdir(d))
    assert [n for n in names if n.startswith("arrays_")] == [
        ckpt_io.shard_payload_name(i, 3) for i in range(3)]
    assert ckpt_io.verify_dir(str(d))
    template = jax.eval_shape(lambda: tree)
    loaded, step = ckpt_io.load(str(tmp_path), template)
    assert step == 5 and _bits_equal(tree, loaded)


def test_single_shard_save_keeps_legacy_layout(tmp_path):
    ckpt_io.save(str(tmp_path), 1, _tree(), n_shards=1)
    d = tmp_path / "step_0000000001"
    assert (d / ckpt_io.PAYLOAD).exists()
    assert ckpt_io.verify_dir(str(d))


@pytest.mark.parametrize("mode", ["bitflip", "truncate", "delete"])
def test_one_bad_shard_untrusts_the_whole_step(tmp_path, mode):
    """Damage to ANY single payload shard of the newest save quarantines
    the whole step; election falls back to the older complete set."""
    ckpt_io.save(str(tmp_path), 3, _tree(3), n_shards=2)
    ckpt_io.save(str(tmp_path), 6, _tree(6), n_shards=2)
    d6 = str(tmp_path / "step_0000000006")
    tfaults.corrupt_checkpoint(d6, mode, shard=1)
    assert not ckpt_io.verify_dir(d6)
    with pytest.raises(ckpt_io.CorruptCheckpointError):
        ckpt_io.load(str(tmp_path), jax.eval_shape(lambda: _tree()), step=6)
    assert ckpt_io.latest_valid(str(tmp_path),
                                quarantine_corrupt=True) == 3
    assert any(".corrupt" in n for n in os.listdir(tmp_path))


def test_torn_manifest_quarantines_step(tmp_path):
    ckpt_io.save(str(tmp_path), 2, _tree(2), n_shards=2)
    ckpt_io.save(str(tmp_path), 4, _tree(4), n_shards=2)
    tfaults.corrupt_checkpoint(str(tmp_path / "step_0000000004"),
                               "manifest")
    assert ckpt_io.latest_valid(str(tmp_path),
                                quarantine_corrupt=True) == 2


def test_write_stage_order_includes_shards_and_fsync(tmp_path):
    stages = []
    with ckpt_io.write_fault_hook(lambda st, p: stages.append(st)):
        ckpt_io.save(str(tmp_path), 1, _tree(), n_shards=2)
    assert stages == ["payload", "shard0", "shard1", "manifest", "fsync",
                      "publish", "done"]
    stages.clear()
    with ckpt_io.write_fault_hook(lambda st, p: stages.append(st)):
        ckpt_io.save(str(tmp_path), 2, _tree(), n_shards=1)
    # legacy layout: no per-shard stages
    assert stages == ["payload", "manifest", "fsync", "publish", "done"]


@pytest.mark.parametrize("stage", ["shard1", "fsync"])
def test_crash_mid_write_never_publishes(tmp_path, stage):
    """A kill at any pre-publish stage — including the new fsync stage
    (S6) and a mid-shard write — leaves the previous save the newest
    valid one and no step directory for the torn save."""
    ckpt_io.save(str(tmp_path), 3, _tree(3), n_shards=2)

    def hook(st, path):
        if st == stage:
            raise InjectedCrash(f"kill at {st}")

    with ckpt_io.write_fault_hook(hook):
        with pytest.raises(InjectedCrash):
            ckpt_io.save(str(tmp_path), 6, _tree(6), n_shards=2)
    assert not (tmp_path / "step_0000000006").exists()
    assert ckpt_io.latest_valid(str(tmp_path)) == 3


# ------------------------------------------------- per-example skip gate

def _gate_loss_fn(tcfg):
    """Finite scalar loss, per-example gate poisoned through the batch:
    isolates the ce_ex path of the skip gate from isfinite(loss)."""
    base = make_loss_fn(CFG, tcfg)

    def loss_fn(params, batch, fisher, rng):
        loss, aux = base(params, batch, fisher, rng)
        aux = dict(aux)
        aux["ce_ex"] = aux["ce_ex"] * batch["gate_poison"]
        return loss, aux

    return loss_fn


@pytest.mark.parametrize("use_kernel", [False, True])
def test_ce_ex_gate_skips_even_when_loss_is_finite(use_kernel):
    """A non-finite PER-EXAMPLE CE skips the step (params and optimizer
    state frozen) even though the scalar loss stays finite — for the jnp
    chain and the fused core's in-kernel SC_OK gate alike.  This is the
    cross-shard agreement bit: every shard computes all(isfinite(ce_ex))
    over the global batch, so one poisoned example anywhere skips the
    step everywhere."""
    tcfg = _tcfg(use_kernel)
    step, st0 = _build(use_kernel, loss_fn=_gate_loss_fn(tcfg))
    step = jax.jit(step)
    clean = np.ones((4,), np.float32)
    poisoned = clean.copy()
    poisoned[1] = np.nan

    b0, b1 = dict(_batch(0)), dict(_batch(1))
    b0["gate_poison"] = clean
    st, _ = step(st0, b0)
    frozen = jax.device_get({"params": st["params"], "opt": st["opt"]})

    b1["gate_poison"] = poisoned
    st, m = step(st, b1)
    assert bool(m["skipped"])
    assert np.isfinite(float(m["loss"]))      # loss alone would not gate
    assert _bits_equal(frozen, {"params": st["params"], "opt": st["opt"]})
    assert int(st["step"]) == 2

    b1["gate_poison"] = clean                  # clean replay applies
    st, m = step(st, b1)
    assert not bool(m["skipped"])
    assert not _bits_equal(frozen,
                           {"params": st["params"], "opt": st["opt"]})


def test_custom_loss_without_ce_ex_degrades_to_loss_gate():
    def loss_fn(params, batch, fisher, rng):
        loss = sum(jnp.sum(l * l) for l in
                   jax.tree_util.tree_leaves(params)) * 1e-6
        return loss, {"ce": loss}

    step, st = _build(loss_fn=loss_fn)
    st, m = jax.jit(step)(st, _batch(0))
    assert not bool(m["skipped"]) and np.isfinite(float(m["loss"]))


# ------------------------------------------------------ eval spike monitor

def test_eval_ce_spike_triggers_coordinated_rollback(tmp_path):
    """S2: a sustained eval-CE spike rolls the run back exactly like a
    train-loss spike, counted separately in ``eval_rollbacks``."""
    step, st = _build()
    calls = {"n": 0}

    def eval_hook(state):
        calls["n"] += 1
        return {"ce": 200.0 if calls["n"] == 6 else 2.0}

    pipe = DataPipeline(lambda s: _batch(s), prefetch=0)
    out = run_loop(step, st, pipe, 16, ckpt_dir=str(tmp_path),
                   ckpt_every=2, eval_every=2, eval_hook=eval_hook,
                   eval_spike_zscore=6.0, eval_spike_warmup=4,
                   eval_spike_patience=1, cooldown_steps=3, **_QUIET)
    pipe.close()
    assert out["eval_rollbacks"] == 1 and out["rollbacks"] == 0
    assert out["data_windows_skipped"] == 1
    assert int(out["state"]["step"]) == 16
    assert float(out["state"]["lr_scale"]) == 1.0


def test_eval_monitor_requires_eval_hook():
    step, st = _build()
    pipe = DataPipeline(lambda s: _batch(s), prefetch=0)
    with pytest.raises(ValueError):
        run_loop(step, st, pipe, 2, ckpt_dir="/tmp/x",
                 eval_spike_zscore=6.0, **_QUIET)
    pipe.close()


# -------------------------------------------------- data-reorder rollback

def test_rollback_reorder_never_refeeds_poisoned_window(tmp_path):
    """S1: with STEP-keyed poison (same batch index is poisoned every
    time it is served), an exact-replay rollback would re-feed the bad
    window; the reordering rollback seeks past it, so each poisoned
    index is served exactly once and the run completes."""
    step, st = _build()
    served = []

    def fn(s):
        served.append(s)
        return _batch(s, poison=1e4 if s in (6, 7) else 1.0)

    pipe = DataPipeline(fn, prefetch=0)
    out = run_loop(step, st, pipe, 12, ckpt_dir=str(tmp_path),
                   ckpt_every=2, spike_zscore=6.0, spike_warmup=4,
                   spike_patience=2, cooldown_steps=3,
                   rollback_reorder=True, **_QUIET)
    pipe.close()
    assert out["rollbacks"] == 1
    assert out["data_windows_skipped"] == 1
    assert served.count(6) == 1 and served.count(7) == 1
    assert int(out["state"]["step"]) == 12


def test_rollback_reorder_false_keeps_exact_replay(tmp_path):
    """Fetch-ordinal poison + rollback_reorder=False reproduces the PR 8
    exact-replay semantics: the replayed window is served again (clean,
    because the fault was transient) and no window is skipped."""
    step, st = _build()
    fetches = {"n": 0}

    def fn(s):
        i = fetches["n"]
        fetches["n"] += 1
        return _batch(s, poison=1e4 if i in (6, 7) else 1.0)

    pipe = DataPipeline(fn, prefetch=0)
    out = run_loop(step, st, pipe, 12, ckpt_dir=str(tmp_path),
                   ckpt_every=2, spike_zscore=6.0, spike_warmup=4,
                   spike_patience=2, cooldown_steps=3,
                   rollback_reorder=False, **_QUIET)
    pipe.close()
    assert out["rollbacks"] == 1
    assert out["data_windows_skipped"] == 0
    assert int(out["state"]["step"]) == 12


# --------------------------------------------------- host-level chaos

def test_host_kill_surfaces_as_timeout_and_heals(tmp_path):
    """A peer host killed mid-run surfaces as a CoordinatorTimeout at
    the next fingerprint heartbeat, the supervisor restarts with a
    replacement host, and the run completes with zero violations."""
    step, _ = _build()
    plan = tfaults.chaos_train_plan(5, n_steps=10, nan_rate=0.0,
                                    stall_rate=0.0, n_crashes=0,
                                    ckpt_crash_save=None,
                                    corrupt_save=None, spike_at=10 ** 6,
                                    n_hosts=2, host_kill_at=4)
    s = tfaults.run_chaos(step, lambda: _build()[1], _batch, plan, 10,
                          str(tmp_path), n_hosts=2)
    assert s["violations"] == []
    assert s["host_kill_timeouts"] == 1 and s["resumes"] >= 1
    assert s["divergence_checks"] >= 1
    assert s["result"] is not None and np.isfinite(s["final_loss"])


def test_straggler_surfaces_as_timeout_and_heals(tmp_path):
    step, _ = _build()
    plan = tfaults.chaos_train_plan(5, n_steps=10, nan_rate=0.0,
                                    stall_rate=0.0, n_crashes=0,
                                    ckpt_crash_save=None,
                                    corrupt_save=None, spike_at=10 ** 6,
                                    n_hosts=3, straggle_at=5)
    s = tfaults.run_chaos(step, lambda: _build()[1], _batch, plan, 10,
                          str(tmp_path), n_hosts=3)
    assert s["violations"] == []
    assert s["straggler_timeouts"] == 1
    assert s["result"] is not None


# ----------------------------------------------------- multi-device mesh

@needs8
def test_one_data_shard_nan_skips_step_on_all_shards():
    """2x4 mesh, batch sharded over the data axis, NaN poisoning ONLY the
    examples of data-shard 0: the step is skipped identically everywhere
    — params stay bit-identical on every device replica — and the
    replica audit finds no divergence."""
    mesh = make_mesh((2, 4), ("data", "model"))
    tcfg = _tcfg()

    base = make_loss_fn(CFG, tcfg)

    def loss_fn(params, batch, fisher, rng):
        _, aux = base(params, batch, fisher, rng)
        aux = dict(aux)
        ce = aux["ce_ex"] * batch["poison_ex"]    # (b,) per-example
        aux["ce_ex"] = ce
        return jnp.mean(ce), aux                  # the poisoned mean

    opt = make_optimizer(tcfg, adamw(constant(1e-2)))
    step = jax.jit(make_train_step(CFG, tcfg, opt, loss_fn=loss_fn))
    rep = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())),
        init_state(lm_init(jax.random.PRNGKey(0), CFG), opt))

    def sharded_batch(poison_ex):
        b = dict(lm_batch(0, 0, 4, 16, CFG.vocab, PERM))
        b["poison_ex"] = np.asarray(poison_ex, np.float32)
        sh = {k: NamedSharding(mesh, P("data") if v.ndim == 1
                               else P("data", None))
              for k, v in b.items()}
        return {k: jax.device_put(v, sh[k]) for k, v in b.items()}

    with mesh:
        frozen = jax.device_get({"params": rep["params"],
                                 "opt": rep["opt"]})
        st, m = step(rep, sharded_batch([np.nan, np.nan, 1.0, 1.0]))
        assert bool(m["skipped"])
        assert _bits_equal(frozen, {"params": st["params"],
                                    "opt": st["opt"]})
        assert replica_divergence(st["params"]) == []
        st, m = step(st, sharded_batch([1.0, 1.0, 1.0, 1.0]))
        assert not bool(m["skipped"])
        assert replica_divergence(st["params"]) == []


@needs8
def test_elastic_restore_across_mesh_shapes_under_corruption(tmp_path):
    """S3: a sharded-payload checkpoint saved from a 2x4-placed tree
    restores bit-exactly onto 1x1 and 4x2 meshes; corrupting one shard
    of the newest save quarantines that WHOLE step first, so the elected
    restore target is the older complete set."""
    mesh = make_mesh((2, 4), ("data", "model"))
    sh2 = NamedSharding(mesh, P("data", "model"))
    sh1 = NamedSharding(mesh, P("model"))

    def placed_tree(seed):
        t = _tree(seed)
        return {"w1": jax.device_put(t["w1"], sh2),
                "w2": jax.device_put(t["w2"], sh2),
                "b": jax.device_put(t["b"], sh1),
                "step": t["step"]}

    good, newest = placed_tree(7), placed_tree(9)
    ckpt_io.save(str(tmp_path), 7, good, n_shards=2)
    ckpt_io.save(str(tmp_path), 9, newest, n_shards=2)
    tfaults.corrupt_checkpoint(str(tmp_path / "step_0000000009"),
                               "delete", shard=0)
    best = ckpt_io.latest_valid(str(tmp_path), quarantine_corrupt=True)
    assert Coordinator().elect_checkpoint(best) == 7

    template = jax.eval_shape(lambda: good)
    want = jax.device_get(good)
    for shape in ((1, 1), (4, 2)):
        m2 = make_mesh(shape, ("data", "model"))
        loaded, s = ckpt_io.load(str(tmp_path), template, step=7)
        assert s == 7
        placed = {
            "w1": jax.device_put(loaded["w1"],
                                 NamedSharding(m2, P("data", "model"))),
            "w2": jax.device_put(loaded["w2"],
                                 NamedSharding(m2, P("data", "model"))),
            "b": jax.device_put(loaded["b"],
                                NamedSharding(m2, P("model"))),
            "step": loaded["step"]}
        assert _bits_equal(want, placed)
        assert replica_divergence(placed) == []
