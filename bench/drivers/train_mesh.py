"""Training cells on a mesh of any shape: LOTION steps back to back
through the program's ``run_loop``, batches from the program's
``DataPipeline``, the state placed on the configuration's ``train.mesh``
by the program's ``shard_setup``.

The loop, its spans, its variants and its compared numbers are those of
``drivers/train.py``; on a (1, 1) mesh the result line is the same.  What
differs is that no device ever holds a whole float32 tree: the weights
the change after the first steps is measured from are made on the
state's parameter shardings, and the plain reference follows the first
steps on the same mesh (``reference_follow``).  The trace is read by
``harness/collectives.py``, whose summary adds the collectives' time.

Besides ``drivers/train.py``'s variants it plants one fault that exists
only on a mesh, ``no_grad_exchange``: the gradient exchange between the
``data`` shards left out (``_no_exchange_loss``).
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.drivers.train import _find, _norm_tree_fn, _WindowDone
from bench.harness import common, trace, work
from bench.harness import weights as W
from bench.harness.collectives import MeshCapture
from bench.harness.compare import leaf_gap, rel_gap, verdict
from bench.harness.model import lm_config


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
    tr = MeshCapture(ctx["args"].trace, spans)

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
        param_sh = state_sh["params"]
        state = jax.jit(lambda s: init_state(W.stacked(s, c), opt),
                        out_shardings=state_sh)(seed_arr)
        loss_fn = None
        if variant == "half_batch":
            full = make_loss_fn(cfg, tcfg)

            def loss_fn(params, batch, fisher, rng):
                half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
                return full(params, half, fisher, rng)
        elif variant == "no_grad_exchange":
            loss_fn = _no_exchange_loss(make_loss_fn(cfg, tcfg), param_sh)
        step = make_train_step(cfg, tcfg, opt, grad_shardings=param_sh,
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
                    p0 = jax.jit(lambda s: W.stacked(s, c),
                                 out_shardings=param_sh)(seed_arr)
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
    compared = reference_check(ctx, c, t, B, S, V, s32, first, rec,
                               param_sh)
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


def _data_shard(shape, spec, mesh):
    """Which ``data`` shard holds each element of a leaf laid out by
    ``spec`` (an int array broadcast to ``shape``), or None for a leaf not
    split over ``data``.  Uneven dimensions are split as GSPMD pads them:
    in chunks of ceil(n / devices along the dimension)."""
    for d, ax in enumerate(spec):
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        if "data" not in axes:
            continue
        sizes = [mesh.shape[a] for a in axes]
        per = -(-shape[d] // math.prod(sizes))
        chunk = jax.lax.broadcasted_iota(jnp.int32, shape, d) // per
        k = axes.index("data")
        return chunk // math.prod(sizes[k + 1:]) % sizes[k]
    return None


def _no_exchange_loss(full, shardings):
    """The planted fault ``no_grad_exchange``: the loss of each ``data``
    shard's rows alone, arranged so that the gradient each shard holds is
    its own rows' (the mean over them) and not the whole batch's, as when
    the reduction of the gradient over ``data`` is left out.  A leaf split
    over ``data`` takes each slice from the shard that holds it; a leaf
    replicated over ``data`` differs between the replicas and is read back
    from the first, so it takes the first shard's.  The loss and the
    forward values are the sound program's; only the gradient is not."""
    mesh = jax.tree.leaves(shardings)[0].mesh
    nd = mesh.shape["data"]
    specs = [s.spec for s in jax.tree.leaves(shardings)]

    def own(w, spec, i):
        owner = _data_shard(w.shape, spec, mesh)
        scale = (jnp.float32(nd if i == 0 else 0) if owner is None
                 else jnp.where(owner == i, nd, 0).astype(w.dtype))
        fixed = jax.lax.stop_gradient(w)
        return fixed + scale * (w - fixed)

    def loss_fn(params, batch, fisher, rng):
        leaves, tdef = jax.tree.flatten(params)
        rows = jax.tree.leaves(batch)[0].shape[0] // nd
        total, auxs = 0.0, []
        for i in range(nd):
            p_i = jax.tree.unflatten(tdef, [own(w, sp, i)
                                            for w, sp in zip(leaves, specs)])
            b_i = jax.tree.map(lambda x: x[i * rows:(i + 1) * rows], batch)
            loss, aux = full(p_i, b_i, fisher, rng)
            total = total + loss / nd
            auxs.append(aux)
        aux = {k: (jnp.concatenate([a[k] for a in auxs]) if k == "ce_ex"
                   else sum(a[k] for a in auxs) / nd) for k in auxs[0]}
        return total, aux
    return loss_fn


def adamw_lotion_update(ref, params, grads, mu, nu, count: int,
                        t: Dict, shardings, keep: bool = True):
    """``ref.adamw_lotion_update`` (its scalars, eligibility and per-leaf
    ``_leaf_update``) with each leaf's moments placed on its sharding on
    the way in and each new weight kept on it.  Between steps the first
    moment stays on the devices and the second is held in host memory
    (``pinned_host``) on the same shardings: the reference's gradient
    program and both moment trees do not fit a v5e together at 40 layers,
    and moving a tree out of the devices is the slow direction.  Moments
    of None are the first step's zeros, made on the devices; with ``keep``
    False the new moments are dropped on the devices (the last step's are
    never read).  Returns (new params, new moments or None, LOTION penalty
    of the pre-update weights)."""
    gn = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))))
    cs = min(1.0, t["clip_norm"] / max(gn, 1e-9))
    bc1, bc2 = 1 - t["b1"] ** count, 1 - t["b2"] ** count
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    gl, shl = jax.tree.leaves(grads), jax.tree.leaves(shardings)
    ml, nl = (jax.tree.leaves(x) if x is not None else [None] * len(gl)
              for x in (mu, nu))
    sc = jax.device_put(
        np.asarray([ref.lr_at(count, t), bc1, bc2, cs, t["b1"], t["b2"],
                    t["eps"], t["weight_decay"]], np.float32),
        NamedSharding(shl[0].mesh, P()))

    def put(x, w, sh):
        if x is None:
            return jnp.zeros(w.shape, jnp.float32, device=sh)
        return jax.device_put(x, sh)

    new_p, new_m, new_n, pens = [], [], [], []
    for (path, w), g, m, n, sh in zip(flat, gl, ml, nl, shl):
        name = jax.tree_util.keystr(path)
        lam = t["lam"] if ref.eligible(name, w) else None
        w2, m2, n2, pv = ref._leaf_update(w, g, put(m, w, sh), put(n, w, sh),
                                          sc, lam)
        new_p.append(jax.device_put(w2, sh))
        if keep:
            new_m.append(jax.device_put(m2, sh))
            new_n.append(jax.device_put(n2,
                                        sh.with_memory_kind("pinned_host")))
        pens.append(pv)
    pen = 0.0
    for pv in pens:
        pen += float(pv)
    unf = jax.tree_util.tree_unflatten
    if not keep:
        return unf(tdef, new_p), None, None, pen
    return unf(tdef, new_p), unf(tdef, new_m), unf(tdef, new_n), pen


def reference_follow(ref, c, t, B, S, V, s32, first, shardings):
    """The plain reference's first steps from the seed's weights and
    batches, on the mesh of ``shardings`` (the program's parameter
    shardings): (losses, first-gradient leaf norms, change leaf norms).
    Weights and gradients are made on those shardings, batch rows over
    ``data``; the second moment is held in host memory between steps."""
    mesh = jax.tree.leaves(shardings)[0].mesh
    seed_arr = jnp.asarray(s32, jnp.int32)
    make = jax.jit(lambda s: W.stacked(s, c), out_shardings=shardings)
    batch_jit = jax.jit(lambda s, i: W.train_batch(s, i, B, S, V),
                        out_shardings=NamedSharding(mesh, P("data")))
    lg = jax.jit(lambda p, b: ref.loss_and_grads(p, b, c, t["logit_chunk"]),
                 out_shardings=(NamedSharding(mesh, P()), shardings))
    params = make(seed_arr)
    mu = nu = None
    losses, grad_n = [], None
    for k in range(1, first + 1):
        t0 = time.perf_counter()
        loss, g = lg(params, batch_jit(seed_arr, k - 1))
        loss = float(loss)
        t1 = time.perf_counter()
        if k == 1:
            grad_n = ref.leaf_norms(g)
        params, mu, nu, pen = adamw_lotion_update(
            ref, params, g, mu, nu, k, t, shardings, keep=k < first)
        del g
        losses.append(loss + pen)
        common.log(f"train: reference step {k}: loss and gradients "
                   f"{t1 - t0:.2f} s, update {time.perf_counter() - t1:.2f} s")
    p0 = make(seed_arr)
    change_n = ref.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return losses, grad_n, change_n


def reference_check(ctx, c, t, B, S, V, s32, first, rec, shardings):
    """``drivers/train.py``'s three compared numbers, the reference
    following on the mesh of ``shardings``.  With the ``control`` variant
    the float8-operand reference stands in the program's place."""
    ref = common.reference(c)
    losses, grad_n, change_n = reference_follow(ref, c, t, B, S, V, s32,
                                                first, shardings)
    common.log(f"train: reference losses {losses}")
    if ctx.get("variant") == "control":
        ref.Precision.fp8 = True
        try:
            cl, cg, cc = reference_follow(ref, c, t, B, S, V, s32, first,
                                          shardings)
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
