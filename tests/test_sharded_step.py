"""One LOTION step on a (data 2, model 2) mesh of four virtual CPU devices
against the same step on one device: the fused LOTION-AdamW kernel runs
per shard (``fused_opt_step_sharded``, Pallas in interpret mode), and the
loss and the updated weights agree to float32 rounding.  The sharded
step's collectives carry the program's scopes in their op metadata.

The device count is fixed when JAX starts, so the step runs in a
subprocess."""

import json
import os
import subprocess
import sys
import textwrap

from repro import spans

ROOT = os.path.join(os.path.dirname(__file__), "..")

STEP = textwrap.dedent("""
    import json, re
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import QuantConfig, QuantPolicy
    from repro.distributed import train_batch_shardings
    from repro.distributed.context import clear_constraints
    from repro.launch import specs as sp
    from repro.launch.mesh import make_mesh
    from repro.launch.train import shard_setup
    from repro.models.lm import LMConfig, lm_init
    from repro.optim import adamw
    from repro.train import (TrainConfig, init_state, make_optimizer,
                             make_train_step)

    assert len(jax.devices()) == 4
    cfg = LMConfig(name="sh", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_ff=128, vocab=64, dtype=jnp.float32)
    tcfg = TrainConfig(quant=QuantConfig(
        method="lotion", fmt_name="int4", lam=1000.0, use_kernel=True,
        policy=QuantPolicy(min_size=256)), clip_norm=1.0)
    LR = 1e-3
    opt = make_optimizer(tcfg, adamw(lambda count: LR))
    params = lm_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    got = {}
    for shape in ((1, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"),
                         devices=jax.devices()[:shape[0] * shape[1]])
        with mesh:
            state_sh = shard_setup(cfg, opt, mesh)
            state = jax.device_put(init_state(params, opt), state_sh)
            b = jax.device_put(batch, train_batch_shardings(
                mesh, sp.train_batch_specs(cfg, 4, 16), 4))
            step = jax.jit(make_train_step(
                cfg, tcfg, opt, grad_shardings=state_sh["params"]))
            text = step.lower(state, b).compile().as_text()
            new, metrics = step(state, b)
            got[shape] = (float(metrics["loss"]), jax.device_get(
                new["params"]), text, new["params"])
        clear_constraints()
    (l1, p1, _, _), (l4, p4, text, live) = got[(1, 1)], got[(2, 2)]
    old = jax.device_get(params)
    worst, stray = 0.0, 0
    for a, b, w in zip(jax.tree.leaves(p4), jax.tree.leaves(p1),
                       jax.tree.leaves(old)):
        worst = max(worst, float(np.linalg.norm((a - w) - (b - w))
                                 / np.linalg.norm(b - w)))
        stray += int(np.sum((np.abs(a - b) > 1e-6)
                            & (np.abs(b - w) >= 0.9 * LR)))
    names = []
    for line in text.splitlines():
        if re.search(r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\\(", line):
            m = re.search(r'op_name="([^"]*)"', line)
            names.append(m.group(1) if m else "")
    spread = max(len({s.device for s in x.addressable_shards})
                 for x in jax.tree.leaves(live))
    print("RESULT " + json.dumps({
        "loss_rel": abs(l4 - l1) / abs(l1), "change_rel": worst,
        "stray": stray,
        "collectives": names, "spread": spread}))
""")


def test_lotion_step_on_a_2x2_mesh_matches_one_device():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", STEP], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    got = json.loads(line[-1][len("RESULT "):])
    assert got["spread"] == 4
    assert got["loss_rel"] <= 1e-5, got["loss_rel"]
    # Adam's first step moves a weight by lr g / (|g| + eps): a gradient
    # element near eps, where float32 sums taken in another order differ,
    # moves by another fraction of lr.  Every weight moved by the full lr
    # agrees to rounding, and each leaf's change to 1e-3 of its norm.
    assert got["stray"] == 0
    assert got["change_rel"] <= 1e-3, got["change_rel"]
    names = got["collectives"]
    assert names
    # the update runs the kernel per shard: its penalty partials are summed
    # over the mesh inside the shard_map; the gradient's move into the
    # update's layout is under grad_sync
    assert any(f"{spans.UPDATE}/shard_map" in n for n in names), names
    scoped = {s for n in names for s in (spans.GRAD_SYNC,
                                         spans.FORWARD_BACKWARD, spans.UPDATE)
              if s in n.split("/") or f"({s})" in n}
    assert {spans.GRAD_SYNC, spans.FORWARD_BACKWARD, spans.UPDATE} <= scoped, \
        names
