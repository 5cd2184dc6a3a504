"""Serving cells: requests through the program's continuous-batching
``Scheduler`` (``submit`` / ``step``), int4 weights and paged int4 KV.

Closed loop (``"loop": "closed"``): ``clients`` callers, each sending its
next request as soon as its last one completes.  Open loop (``"open"``):
requests sent at their due times, whatever the server is doing; each is
timed from when it was due.  Set-up builds the weights on the device,
builds the scheduler and warms it with the cell's own traffic; the window
then runs for ``--seconds`` on the wall clock.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, trace, traffic, work
from bench.harness import weights as W
from bench.harness.compare import verdict
from bench.harness.model import lm_config


def served_params(seed_arr, c: Dict, sv: Dict):
    """The float32 weights of the seed, quantized by the program's own
    ``quantize_params`` layer by layer inside one jitted call, so the
    float32 tree never sits whole on the device."""
    from repro.core import QuantPolicy, get_format, quantize_params
    fmt = get_format(sv["weights"].split(":")[1])
    policy = QuantPolicy(min_size=sv["min_size"])

    def build(s):
        ke, kl, _ = W.keys(s)

        def one(l):
            tree = {"stage": {"b0_attn": W.layer(kl, c, l)}}
            return quantize_params(tree, fmt, policy, -1,
                                   mode="rtn")["stage"]["b0_attn"]

        stage = jax.lax.map(one, jnp.arange(c["num_hidden_layers"]))
        return quantize_params(
            {"embed": W.embed(ke, c), "stage": {},
             "final_norm_scale": W.final_norm(ke, c)}, fmt, policy, -1,
            mode="rtn") | {"stage": {"b0_attn": stage}}

    return jax.jit(build)(seed_arr)


class Clients:
    """Submits requests and stamps when each was due, got its first token
    and completed (host clock, seconds from ``t0``)."""

    def __init__(self, sch, spans):
        self.sch, self.spans = sch, spans
        self.info: Dict[int, Dict] = {}
        self.waiting_first: set = set()
        self.active: set = set()
        self.acc = None          # per-layer accounting while traced
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, req: Dict, due: float, mnt: int = None) -> int:
        with self.spans.span("Scheduler.submit"):
            rid = self.sch.submit(req["prompt"], mnt or req["max_new"],
                                  arrival=due)
        self.info[rid] = {"due": due}
        self.waiting_first.add(rid)
        self.active.add(rid)
        return rid

    def step(self, now: float = None) -> List[int]:
        acc = self.acc
        if acc is not None:
            before = {r: len(self.sch.requests[r].out) for r in self.active}
        with self.spans.span("Scheduler.step"):
            done = self.sch.step(now=now)
        t = self.now()
        for rid in list(self.waiting_first):
            if self.sch.requests[rid].out:
                self.info[rid]["first"] = t
                self.waiting_first.discard(rid)
        ids = []
        for r in done:
            self.info[r.rid]["last"] = t
            self.info[r.rid]["state"] = r.state
            self.active.discard(r.rid)
            ids.append(r.rid)
        if acc is not None:
            for rid, n0 in before.items():
                req = self.sch.requests[rid]
                plen = len(req.prompt)
                if n0 == 0 and req.out:
                    acc["prefill_flops"] += work.prefill_flops(
                        acc["c"], 0, plen)
                    if req.t_admit is not None:
                        acc["queue_waits_ms"].append(
                            (req.t_admit - req.arrival) * 1e3)
                acc["decode_ctx"] += [plen + j for j in
                                      range(max(n0, 1), len(req.out))]
        return ids


def _p95(xs: List[float]) -> float:
    """Nearest-rank 95th percentile; a request that never answered counts
    as infinitely late."""
    xs = sorted(xs)
    return float(xs[max(0, int(np.ceil(0.95 * len(xs))) - 1)])


def run(ctx: Dict) -> Dict:
    from repro.serve import Scheduler, SchedulerConfig, ServeConfig
    from repro.serve.slots import COMPLETED

    args, plan, pk = ctx["args"], ctx["plan"], ctx["peaks"]
    variant = ctx.get("variant")
    c, mix, sv = plan["config"], plan["mix"], plan["config"]["serve"]
    s32 = common.seed32(args.seed)
    seed_arr = jnp.asarray(s32, jnp.int32)
    cfg = lm_config(c)
    params = served_params(seed_arr, c, sv)
    scfg = ServeConfig(weights="fp32", use_kernel=sv["use_kernel"],
                       kv_quant=sv["kv_quant"],
                       temperature=sv["temperature"],
                       act_fmt="int8" if variant == "w4a8" else None)
    sch = Scheduler(cfg, params, scfg, SchedulerConfig(
        n_slots=mix["n_slots"], steps_per_tick=sv["steps_per_tick"],
        cache_len=mix["cache_len"], prefill_chunk=sv["prefill_chunk"],
        prefix_cache=sv["prefix_cache"],
        prefix_cache_blocks=sv["prefix_cache_blocks"], paged=True,
        block_size=sv["block_size"]))
    stream = traffic.Requests(mix, args.seed, c["vocab_size"])
    spans = trace.Spans()
    cl = Clients(sch, spans)
    tr = trace.Capture(args.trace, spans)
    closed = mix["loop"] == "closed"

    # ---- set-up: warm every shape this traffic uses ------------------
    # prompts spanning the mix's sizes, the longest included, so every
    # chunk index the window can reach has run once
    sizes = np.sort(traffic.quantile_sizes(mix["prompt"], mix["n_sizes"]))
    picks = np.linspace(0, len(sizes) - 1, mix["warmup_requests"])
    for j, k in enumerate(picks):
        toks = traffic.rng_for(args.seed, 9, j).integers(
            1, c["vocab_size"], size=int(sizes[int(k)]))
        cl.submit({"prompt": toks.tolist(),
                   "max_new": 2 * sv["steps_per_tick"]}, cl.now())
    while sch.has_work():
        cl.step(now=None if closed else cl.now())
    if closed:
        for i in range(mix["clients"]):
            req = stream.next()
            cl.submit(req, cl.now(), traffic.residual_budget(
                req["max_new"], i))
        for _ in range(mix["warmup_steps"]):
            for _rid in cl.step():
                cl.submit(stream.next(), cl.now())

    compiles = common.CompileCount()

    # ---- window -------------------------------------------------------
    t0 = cl.now()
    setup_s = time.perf_counter() - ctx["t_start"]
    emitted0 = {rid: len(r.out) for rid, r in sch.requests.items()}
    counts0 = (sch.n_ticks, sch.n_prefills, sch.prefill_tokens_computed)
    window_ids: List[int] = []
    nxt = None if closed else stream.next()
    lateness = 0.0
    acc = {"c": c, "decode_ctx": [], "prefill_flops": 0.0,
           "queue_waits_ms": []}
    compiles.on = True
    tr.start()
    if tr.active:
        cl.acc = acc
    counts_tr0 = counts_tr1 = counts0
    while True:
        now = cl.now()
        if now - t0 >= args.seconds:
            break
        if tr.active and now - t0 >= trace.TRACE_S:
            counts_tr1 = (sch.n_ticks, sch.n_prefills,
                          sch.prefill_tokens_computed)
            tr.stop()
            cl.acc = None
        if closed:
            for _rid in cl.step():
                window_ids.append(_rid)
                cl.submit(stream.next(), cl.now())
        else:
            while t0 + nxt["due"] <= cl.now():
                due = t0 + nxt["due"]
                lateness = max(lateness, cl.now() - due)
                window_ids.append(cl.submit(nxt, due))
                nxt = stream.next()
            if sch.has_work():
                cl.step(now=cl.now())
            else:
                wait = min(t0 + nxt["due"], t0 + args.seconds) - cl.now()
                if wait > 0:
                    with spans.span("generator.wait"):
                        time.sleep(wait)
    t1 = cl.now()
    compiles.on = False
    window_s = t1 - t0
    if tr.active:
        counts_tr1 = (sch.n_ticks, sch.n_prefills,
                      sch.prefill_tokens_computed)
        tr.stop()
        cl.acc = None
    emitted = sum(len(r.out) - emitted0.get(rid, 0)
                  for rid, r in sch.requests.items())
    counts1 = (sch.n_ticks, sch.n_prefills, sch.prefill_tokens_computed)

    # ---- drain: every request of the window gets its answer ----------
    if not closed:
        pending = [r for r in window_ids
                   if cl.info[r].get("last") is None]
        t_drain = cl.now()
        while pending and cl.now() - t_drain < mix["drain_s"]:
            while t0 + nxt["due"] <= cl.now():
                cl.submit(nxt, t0 + nxt["due"])
                nxt = stream.next()
            if sch.has_work():
                cl.step(now=cl.now())
            pending = [r for r in pending if cl.info[r].get("last") is None]
    mem = common.memory_peak(common.memory_stats(jax.devices()[:1]))

    done = [r for r in window_ids
            if cl.info[r].get("state") == COMPLETED]
    failed = len(window_ids) - len(done)
    tpot = [(cl.info[r]["last"] - cl.info[r]["first"])
            / (len(sch.requests[r].out) - 1) * 1e3
            for r in done if len(sch.requests[r].out) > 1]
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": emitted / window_s,
           "completed_tokens_per_s": sum(
               len(sch.requests[r].out) for r in done) / window_s,
           "tpot_p95_ms": _p95(tpot) if tpot else float("inf")}
    if not closed:
        ttft = [(cl.info[r]["first"] - cl.info[r]["due"]) * 1e3
                for r in window_ids if "first" in cl.info[r]]
        ttft += [float("inf")] * (len(window_ids) - len(ttft))
        e2e["ttft_p95_ms"] = _p95(ttft)
    common.log(f"serve: window {window_s:.3f} s, {len(window_ids)} "
               f"requests, {len(done)} completed, {emitted} tokens, "
               f"ticks {counts1[0] - counts0[0]}, chunks "
               f"{counts1[1] - counts0[1]}, generator late by at most "
               f"{lateness * 1e3:.1f} ms, compiles in it {compiles.n}, setup "
               f"{setup_s:.2f} s, {e2e}")

    # ---- correctness: reference over a sample of finished requests ----
    finished = [(rid, sch.requests[rid]) for rid in done]
    sample = _sample(finished, ctx.get("sample") or mix["sample"],
                     args.seed)
    seqs = [(list(r.prompt), list(r.out)) for _, r in sample]
    del sch, params, cl
    gc.collect()
    t_ref = time.perf_counter()
    if variant == "no_check":          # the knee sweep (bench/sweep.py)
        seqs = []
    gaps = reference_gaps(c, s32, seqs, control=variant == "control")
    common.log(f"serve: reference over {len(seqs)} requests, "
               f"{sum(len(o) for _, o in seqs)} served tokens, "
               f"{time.perf_counter() - t_ref:.1f} s")
    if variant == "control":
        common.log(f"serve: the program's own tokens read "
                   f"{gap_stats(gaps['served'])}")
    if ctx.get("dump"):
        np.savez(ctx["dump"], seeds=np.array([args.seed]),
                 **{f"{k}_{i}": g for k, gs in gaps.items()
                    for i, g in enumerate(gs)})
    stats = gap_stats(gaps["control" if variant == "control" else "served"])
    compared = [{"name": k, "value": stats[k], "limit": v}
                for k, v in plan["limits"].items()]
    out = {"e2e": e2e, "memory_peak_bytes": mem,
           "attempted": len(window_ids), "failed": failed,
           "compared": compared, "correct": verdict(compared)}
    if args.trace:
        s = tr.finish()
        ticks = counts_tr1[0] - counts_tr0[0]
        chunks = counts_tr1[1] - counts_tr0[1]
        bw, fl = pk["hbm_bytes_per_s"], pk["bf16_flops_per_s"]
        decode_rows = ticks * sv["steps_per_tick"]
        ctxs = acc["decode_ctx"]
        out.update(busy_s=s["busy_s"], window_s=s["window_s"],
                   breakdown=s["breakdown"])
        out["layer"] = dict(
            trace=s, peaks=pk, chips=1,
            model_flops=sum(work.token_flops(c, x) for x in ctxs)
            + acc["prefill_flops"],
            wqt_matmul_bound_s=work.wqt_matmul_bound_s(
                c, mix["n_slots"], decode_rows, fl, bw)
            + work.wqt_matmul_bound_s(c, sv["prefill_chunk"], chunks, fl, bw),
            decode_attn_bound_s=work.decode_attn_bound_s(c, ctxs, fl, bw),
            queue_waits_ms=acc["queue_waits_ms"],
            steps=tr.count(spans, "Scheduler.step"),
            prefill_tokens=counts_tr1[2] - counts_tr0[2])
    return out


def _sample(finished, spec: Dict, seed: int):
    """The longest finished request and others drawn from the seed, up to
    ``spec["requests"]``, more if they hold fewer than ``min_tokens``
    served tokens together."""
    if not finished:
        return []
    order = sorted(finished, key=lambda x: (-len(x[1].out), x[0]))
    rest = order[1:]
    perm = traffic.rng_for(seed, 7).permutation(len(rest))
    pick = [order[0]]
    for i in perm:
        if (len(pick) >= spec["requests"]
                and sum(len(r.out) for _, r in pick) >= spec["min_tokens"]):
            break
        pick.append(rest[i])
    return pick


TAIL_GAP = 0.15     # logits; a served token this far below the best is rare


def gap_stats(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The numbers compared, over every served token of the sample: the
    widest gap, and the share of tokens whose gap passes ``TAIL_GAP``.
    The widest gap is set by a few int4 KV codes that round the other way
    and barely tells bfloat16 activations from int8 ones; the share of the
    tail counts every error that large, and does (PERF.md, section 2).
    No token to compare reads infinite."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if g.size == 0 or not np.all(np.isfinite(g)):
        return {"served_gap_max": float("inf"),
                "served_tail_share": float("inf")}
    return {"served_gap_max": float(np.max(g)),
            "served_tail_share": float(np.mean(g > TAIL_GAP))}


def reference_gaps(c: Dict, s32: int, seqs, control: bool = False
                   ) -> Dict[str, List[np.ndarray]]:
    """Per request of the sample, at each served token, the gap between
    the reference's best logit and its logit of the served token
    (``served``).  With ``control``, also the gap of the token that the
    float8-operand reference puts first at the same positions
    (``control``)."""
    ref = common.reference(c)
    seed_arr = jnp.asarray(s32, jnp.int32)
    fc = ref.frozen(c)
    out: Dict[str, List[np.ndarray]] = {"served": []}
    if control:
        out["control"] = []
    for prompt, served in seqs:
        toks = prompt + served
        n = len(toks)
        pad = -(-n // 1024) * 1024
        arr = np.zeros((1, pad), np.int32)
        arr[0, :n] = toks
        args = (seed_arr, fc, jnp.asarray(arr), jnp.int32(len(prompt)))
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        if control:
            g, low = (np.asarray(x)[at] for x in ref.control_gaps(*args))
            out["control"].append(low)
        else:
            g = np.asarray(ref.served_gaps(*args))[at]
        out["served"].append(g)
    return out
