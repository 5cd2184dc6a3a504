"""Training cells: LOTION steps back to back through the program's
``run_loop``, batches from the program's ``DataPipeline``.

One object is built in set-up: the compiled step with its state.  Its
first steps (``first_steps`` of the mix) run through the same
``run_loop`` call and feed as the window, and are what the reference
follows; the window then continues that same loop for ``--seconds``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, trace, work
from bench.harness import weights as W
from bench.harness.compare import leaf_gap, rel_gap, verdict
from bench.harness.model import lm_config


class _WindowDone(Exception):
    pass


def _norm_tree_fn():
    """Per-leaf norms, stacked leaves split per layer (same keys as the
    reference's ``leaf_norms``)."""
    def f(tree):
        out = {}
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            name = jax.tree_util.keystr(path)
            x = x.astype(jnp.float32)
            out[name] = (jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(x.shape[0], -1)), axis=1)) if "stage" in name
                else jnp.sqrt(jnp.sum(jnp.square(x)))[None])
        return out
    return f


def _find(opt_state, key: str):
    """The optimizer state's ``key`` entry (``mu``: the first moment tree,
    ``gnorm``: the gradient's global norm), in the fused core's flat dict
    or in a chain's tuple of link states."""
    if isinstance(opt_state, dict) and key in opt_state:
        return opt_state[key]
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            got = _find(s, key)
            if got is not None:
                return got
    return None


def run(ctx: Dict) -> Dict:
    from repro.core import QuantConfig, QuantPolicy
    from repro.data import DataPipeline
    from repro.distributed import train_batch_shardings
    from repro.distributed.context import clear_constraints
    from repro.launch import specs as sp
    from repro.launch.mesh import make_mesh
    from repro.launch.train import shard_setup
    from repro.models.lm import lm_init
    from repro.optim import adamw, cosine_with_warmup
    from repro.train import (TrainConfig, init_state, make_optimizer,
                             make_train_step, run_loop)
    from repro.train.loop import make_loss_fn

    args, plan, pk = ctx["args"], ctx["plan"], ctx["peaks"]
    variant = ctx.get("variant")
    c, mix, t = plan["config"], plan["mix"], plan["config"]["train"]
    B, S, V = mix["batch"], mix["seq"], c["vocab_size"]
    first = mix["first_steps"]
    s32 = common.seed32(args.seed)
    seed_arr = jnp.asarray(s32, jnp.int32)
    cfg = lm_config(c, act_fmt="int8" if variant == "int8_act" else None)
    q = QuantConfig(method=t["method"], fmt_name=t["fmt"], lam=t["lam"],
                    use_kernel=t["use_kernel"],
                    policy=QuantPolicy(min_size=t["min_size"]),
                    penalty_placement=t["placement"])
    tcfg = TrainConfig(quant=q, clip_norm=t["clip_norm"],
                       attn_chunk=t["attn_chunk"],
                       logit_chunk=t["logit_chunk"])
    opt = make_optimizer(tcfg, adamw(
        cosine_with_warmup(t["lr"], t["warmup"], t["total_steps"]),
        b1=t["b1"], b2=t["b2"], eps=t["eps"],
        weight_decay=t["weight_decay"]))
    mesh = make_mesh(tuple(t["mesh"]), ("data", "model"))
    n_dev = int(np.prod(t["mesh"]))

    want = jax.tree.structure(jax.eval_shape(
        lambda k: lm_init(k, cfg), jax.random.PRNGKey(0)))
    have = jax.tree.structure(jax.eval_shape(
        lambda s: W.stacked(s, c), seed_arr))
    if want != have:
        raise ValueError(f"benchmark weights {have} do not match the "
                         f"program's parameter tree {want}")

    batch_jit = jax.jit(lambda s, i: W.train_batch(s, i, B, S, V))
    norms = jax.jit(_norm_tree_fn())
    b1 = t["b1"]
    rec: Dict = {"losses": [], "window_steps": 0, "skipped": 0, "n": 0}
    compiles = common.CompileCount()
    spans = trace.Spans()
    tr = trace.Capture(ctx["args"].trace, spans)

    class Feed:
        """The program's pipeline, with a host span around each fetch."""

        def __init__(self, pipe):
            self.pipe = pipe

        def __iter__(self):
            return self

        def __next__(self):
            with spans.span("pipeline.next"):
                b = next(self.pipe)
            spans.open("train_step")
            return b

        def seek(self, s):
            self.pipe.seek(s)

    with mesh:
        state_sh = shard_setup(cfg, opt, mesh)
        state = jax.jit(lambda s: init_state(W.stacked(s, c), opt),
                        out_shardings=state_sh)(seed_arr)
        loss_fn = None
        if variant == "half_batch":
            full = make_loss_fn(cfg, tcfg)

            def loss_fn(params, batch, fisher, rng):
                half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
                return full(params, half, fisher, rng)
        step = make_train_step(cfg, tcfg, opt,
                               grad_shardings=state_sh["params"],
                               loss_fn=loss_fn)
        batch_sh = train_batch_shardings(
            mesh, sp.train_batch_specs(cfg, B, S), B)
        pipe = DataPipeline(lambda i: batch_jit(seed_arr, i),
                            sharding=batch_sh, prefetch=1)
        feed = Feed(pipe)
        clock = {}

        def hook(st, metrics):
            spans.close("train_step")
            rec["n"] += 1
            n = rec["n"]
            if n <= first:
                rec["losses"].append(float(metrics["loss"]))
                if n == 1:
                    gn = float(_find(st["opt"], "gnorm"))
                    cs = min(1.0, t["clip_norm"] / max(gn, 1e-9))
                    mu_n = jax.device_get(norms(_find(st["opt"], "mu")))
                    rec["grad_norms"] = {k: v / ((1 - b1) * cs)
                                         for k, v in mu_n.items()}
                if n == first:
                    p0 = jax.jit(lambda s: W.stacked(s, c))(seed_arr)
                    rec["change_norms"] = jax.device_get(norms(
                        jax.tree.map(jnp.subtract, st["params"], p0)))
                    del p0
                    jax.block_until_ready(st["params"])
                    clock["t0"] = time.perf_counter()
                    clock["setup_s"] = clock["t0"] - ctx["t_start"]
                    compiles.on = True
                    tr.start()
                return
            rec["window_steps"] += 1
            rec["skipped"] += int(bool(metrics["skipped"]))
            now = time.perf_counter()
            if tr.active and now - clock["t0"] >= trace.TRACE_S:
                tr.stop(now)
            if now - clock["t0"] >= args.seconds:
                clock["t1"] = now
                compiles.on = False
                clock["mem"] = common.memory_stats(jax.devices()[:n_dev])
                raise _WindowDone

        try:
            run_loop(step, state, feed, 1 << 30, log_every=0,
                     log=common.log, step_hook=hook)
        except _WindowDone:
            pass
        window_s = clock["t1"] - clock["t0"]
        if tr.active:
            tr.stop(clock["t1"])
        pipe.close()
        del state
        mem = common.memory_peak(clock["mem"])
    clear_constraints()
    gc.collect()

    tokens = rec["window_steps"] * B * S
    e2e = {"train_tokens_per_s": tokens / window_s,
           "setup_s": clock["setup_s"]}
    common.log(f"train: {rec['window_steps']} steps in {window_s:.3f} s, "
               f"losses {rec['losses']}, compiles in the window "
               f"{compiles.n}, setup {clock['setup_s']:.2f} s")

    t_ref = time.perf_counter()
    compared = reference_check(ctx, c, t, B, S, V, s32, first, rec)
    common.log(f"train: reference {time.perf_counter() - t_ref:.1f} s")

    out = {"e2e": e2e, "memory_peak_bytes": mem,
           "attempted": rec["window_steps"], "failed": rec["skipped"],
           "compared": compared, "correct": verdict(compared)}
    if args.trace:
        summary = tr.finish()
        steps_traced = summary["steps"] = tr.count(spans, "train_step")
        flops = work.train_step_flops(c, B, S) * steps_traced
        out.update(busy_s=summary["busy_s"], window_s=summary["window_s"],
                   breakdown=summary["breakdown"])
        out["layer"] = dict(
            trace=summary, peaks=pk, chips=n_dev,
            train_flops=flops,
            opt_step_bound_s=steps_traced * work.opt_step_bytes(c) / n_dev
            / pk["hbm_bytes_per_s"])
    return out


def reference_follow(ref, c, t, B, S, V, s32, first):
    """The plain reference's first steps from the seed's weights and
    batches: (losses, first-gradient leaf norms, change leaf norms)."""
    seed_arr = jnp.asarray(s32, jnp.int32)
    make = jax.jit(lambda s: W.stacked(s, c))
    batch_jit = jax.jit(lambda s, i: W.train_batch(s, i, B, S, V))
    lg = jax.jit(lambda p, b: ref.loss_and_grads(p, b, c, t["logit_chunk"]))
    params = make(seed_arr)
    mu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    nu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    losses, grad_n = [], None
    for k in range(1, first + 1):
        loss, g = lg(params, batch_jit(seed_arr, k - 1))
        if k == 1:
            grad_n = ref.leaf_norms(g)
        params, mu, nu, pen, _ = ref.adamw_lotion_update(
            params, g, mu, nu, k, t)
        del g
        losses.append(float(loss) + pen)
    p0 = make(seed_arr)
    change_n = ref.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return losses, grad_n, change_n


def reference_check(ctx, c, t, B, S, V, s32, first, rec):
    """The plain reference follows the same first steps from the same
    weights and batches; three numbers are compared.  With the ``control``
    variant the float8-operand reference stands in the program's place."""
    ref = common.reference(c)
    losses, grad_n, change_n = reference_follow(ref, c, t, B, S, V, s32,
                                                first)
    common.log(f"train: reference losses {losses}")
    if ctx.get("variant") == "control":
        ref.Precision.fp8 = True
        try:
            cl, cg, cc = reference_follow(ref, c, t, B, S, V, s32, first)
        finally:
            ref.Precision.fp8 = False
        rec = dict(rec, losses=cl, grad_norms=cg, change_norms=cc)
    limits = ctx["plan"]["limits"]
    gmed = float(np.median(np.concatenate(list(grad_n.values()))))
    moved = {k: grad_n[k] >= 1e-3 * gmed for k in grad_n}
    return [
        {"name": "loss_rel_gap", "value": rel_gap(rec["losses"], losses),
         "limit": limits["loss_rel_gap"]},
        {"name": "grad_leaf_gap",
         "value": leaf_gap(rec["grad_norms"], grad_n),
         "limit": limits["grad_leaf_gap"]},
        {"name": "change_leaf_gap",
         "value": leaf_gap(rec["change_norms"], change_n, moved),
         "limit": limits["change_leaf_gap"]},
    ]
