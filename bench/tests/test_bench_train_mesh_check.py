"""The 40-layer cell's harness (``drivers/train_mesh.py``) at a CPU size:
on a (2, 2) mesh of four virtual CPU devices a sound run is correct and
each planted fault turns ``correct`` false, and the reference followed on
the mesh is the one-device reference's; on a (1, 1) mesh the driver's
compared numbers are ``drivers/train.py``'s.

The four-device runs are subprocesses: the device count is fixed when JAX
starts (``XLA_FLAGS``), and the test process has one CPU device."""

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "train.granite-3-2b-40L.2x2.b8x2048"
SEED = "2147483659"
ARGS = ["--workload", CELL, "--seed", SEED, "--seconds", "1",
        "--trace", "0"]


def _plan(mp, mesh, driver="train_mesh"):
    p = tiny.patch(mp, CELL)
    p["config"]["train"]["mesh"] = list(mesh)
    p["config"]["driver"] = driver
    return p


def _child(which: str) -> None:
    """One four-device check, run in a subprocess; prints its result as
    the line ``RESULT <json>``."""
    import contextlib
    import io

    import jax
    import numpy as np

    assert len(jax.devices()) == 4, jax.devices()
    mp = types.SimpleNamespace(setattr=setattr)
    plan = _plan(mp, (2, 2))
    if which == "reference":
        from bench.drivers import train_mesh as tm
        from bench.harness import common
        from bench.harness import weights as W
        from repro.distributed import params_shardings
        from repro.launch.mesh import make_mesh
        c, t, mix = plan["config"], plan["config"]["train"], plan["mix"]
        shapes = jax.eval_shape(lambda s: W.stacked(s, c), 0)
        got = {}
        for mesh in ((2, 2), (1, 1)):
            m = make_mesh(mesh, ("data", "model"))
            got[mesh] = tm.reference_follow(
                common.reference(c), c, t, mix["batch"], mix["seq"],
                c["vocab_size"], common.seed32(int(SEED)),
                mix["first_steps"], params_shardings(m, shapes))
        (la, ga, ca), (lb, gb, cb) = got[(2, 2)], got[(1, 1)]

        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.max(np.abs(a - b) / np.abs(b)))
        res = {"loss": rel(la, lb),
               "grad": max(rel(ga[k], gb[k]) for k in gb),
               "change": max(rel(ca[k], cb[k]) for k in cb),
               "keys": sorted(ga) == sorted(gb) and sorted(ca) == sorted(cb)}
        print("RESULT " + json.dumps(res), flush=True)
        return
    variant = None
    if which == "frozen":
        import repro.train as rt
        make = rt.make_train_step

        def frozen(*a, **k):
            step = make(*a, **k)

            def same_state(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return same_state
        rt.make_train_step = frozen
    elif which in ("half_batch", "no_grad_exchange"):
        variant = which
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(ARGS, device_check=False, variant=variant)
    assert rc == 0
    print("RESULT " + buf.getvalue().strip().splitlines()[-1], flush=True)


def _four_devices(which: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    code = ("import sys; from bench.tests.test_bench_train_mesh_check "
            "import _child; _child(sys.argv[1])")
    p = subprocess.run([sys.executable, "-c", code, which], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sound_run_on_a_2x2_mesh_is_correct():
    out = _four_devices("sound")
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4


def test_state_left_unchanged_is_caught_on_a_2x2_mesh():
    out = _four_devices("frozen")
    assert out["correct"] is False
    assert out["compared"]["change_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_is_caught_on_a_2x2_mesh():
    out = _four_devices("half_batch")
    assert out["correct"] is False, out["compared"]


def test_gradient_exchange_left_out_is_caught_on_a_2x2_mesh():
    """Each ``data`` shard updates its weights from its own rows'
    gradient, as when the gradient's reduction over ``data`` is left out:
    the first gradient's leaf norms already part from the reference's."""
    out = _four_devices("no_grad_exchange")
    assert out["correct"] is False, out["compared"]
    grad = out["compared"]["grad_leaf_gap"]
    assert grad["value"] > grad["limit"], out["compared"]


def test_mesh_reference_follows_the_one_device_reference():
    """The reference on a (2, 2) mesh (weights, gradients and updates on
    the program's parameter shardings) and on one device: the same
    losses and leaf norms to float32 rounding."""
    got = _four_devices("reference")
    assert got["keys"]
    for k in ("loss", "grad", "change"):
        assert got[k] <= 1e-5, got


def test_one_device_mesh_gives_train_drivers_numbers_exactly(monkeypatch,
                                                             capsys):
    """On a (1, 1) mesh the mesh driver and ``drivers/train.py`` run the
    same programs on the same numbers: every compared number is equal."""
    got = {}
    for driver in ("train", "train_mesh"):
        _plan(monkeypatch, (1, 1), driver)
        assert run.main(ARGS, device_check=False) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["correct"] is True, out["compared"]
        got[driver] = {k: v["value"] for k, v in out["compared"].items()}
    assert got["train_mesh"] == got["train"]


def test_gradient_without_exchange_takes_each_slice_from_its_shard():
    """``no_grad_exchange``'s loss: the loss of the whole batch, and a
    gradient whose slices split over ``data`` each come from the rows of
    the shard that holds them (the mean over those rows); a leaf
    replicated over ``data`` takes the first shard's rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from bench.drivers import train_mesh as tm

    mesh = types.SimpleNamespace(shape={"data": 2, "model": 2})
    sh = {"a": types.SimpleNamespace(spec=P(None, ("data", "model")),
                                     mesh=mesh),
          "b": types.SimpleNamespace(spec=P(), mesh=mesh)}

    def full(params, batch, fisher, rng):
        x = batch["x"]
        per_row = (jnp.sum(x @ params["a"], 1)
                   + x[:, :3] @ params["b"]) ** 2
        return jnp.mean(per_row), {"ce": jnp.mean(per_row),
                                   "ce_ex": per_row}

    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(4, 10)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    batch = {"x": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
    fault = tm._no_exchange_loss(full, sh)
    (loss, aux), g = jax.value_and_grad(fault, has_aux=True)(
        params, batch, None, None)
    whole, _ = full(params, batch, None, None)
    assert float(loss) == pytest.approx(float(whole), rel=1e-6)
    assert aux["ce_ex"].shape == (8,)
    halves = [jax.grad(lambda p, b: full(p, b, None, None)[0])(
        params, {"x": batch["x"][4 * i:4 * (i + 1)]}) for i in range(2)]
    # 10 columns over 4 devices: chunks of 3 (padded), data-major
    # ("data", "model"): chunks 0, 1 are shard 0's, 2, 3 shard 1's
    np.testing.assert_allclose(g["a"][:, :6], halves[0]["a"][:, :6],
                               rtol=1e-5)
    np.testing.assert_allclose(g["a"][:, 6:], halves[1]["a"][:, 6:],
                               rtol=1e-5)
    np.testing.assert_allclose(g["b"], halves[0]["b"], rtol=1e-5)
