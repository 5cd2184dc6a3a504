"""Distributed serving launcher: sharded params + KV cache on a mesh,
batched prefill+decode (the execution twin of the decode dry-run cells).

``--weights rtn:int4`` now means *stored* int4: integer-format casts keep
their packed codes + scales as QTensor parameters (sharded congruently by
the same rule set as the dense weights) and every matmul streams the
codes through the wq_matmul kernel / jnp fallback — no dense weight
materialization on the serving path.  ``--store dense`` restores the
legacy dequantized-at-load behavior.

    REPRO_FORCE_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
        --arch granite-3-2b --smoke --mesh 2x4 --batch 8 --prompt-len 32 \
        --weights rtn:int4

``--scheduler`` swaps the static prefill+decode loop for an offered-load
replay (Poisson arrivals) of the continuous-batching scheduler vs the
static barrier server at equal slot count (``--n-slots``,
``--steps-per-tick``, ``--arrival-rate``, ``--n-requests``); ``--kv-quant
[int8|int4]`` selects the quantized KV cache; ``--prefill-chunk N``
(+ ``--prefix-cache``) enables chunked admission and shared-prefix KV
reuse (DESIGN.md §8).

``--chaos`` replays a seeded fault-injection schedule (logit-NaN slots,
straggler ticks, prefix-cache eviction storms, malformed and burst
submissions) against the fault-tolerant scheduler on a deterministic
virtual clock, auditing the lifecycle invariants after every tick and
exiting nonzero on any violation (DESIGN.md §10).
"""

from __future__ import annotations

import argparse
import os
import time

if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count="
        f"{os.environ['REPRO_FORCE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import (QuantPolicy, cast_params, get_format,  # noqa: E402
                        param_nbytes, quantize_params, qtensor_act_fmt,
                        qtensor_use_kernel)
from repro.core.formats import IntFormat  # noqa: E402
from repro.distributed import params_shardings  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import mesh_from_arg  # noqa: E402
from repro.models.lm import lm_decode, lm_init, lm_prefill  # noqa: E402


def _replay(cfg, params, args, use_kernel, kv_quant, stored_bytes,
            dense_bytes):
    """Offered-load replay: static barrier batching vs the continuous
    scheduler at equal slot count (``params`` arrive weight-prepared and
    sharded, so the serve configs run them as-is)."""
    from repro.serve import Engine, Scheduler, SchedulerConfig, ServeConfig
    from repro.serve.replay import (compare, poisson_workload,
                                    replay_continuous, replay_static)

    scfg = ServeConfig(weights="fp32", use_kernel=use_kernel,
                       kv_quant=kv_quant, act_fmt=args.act_fmt,
                       max_new_tokens=args.new_tokens)
    engine = Engine(cfg, params, scfg)
    cache_len = args.prompt_len + args.new_tokens
    if args.paged and cache_len % args.block_size:
        cache_len += args.block_size - cache_len % args.block_size
    sch = Scheduler(cfg, params, scfg, SchedulerConfig(
        n_slots=args.n_slots, steps_per_tick=args.steps_per_tick,
        cache_len=cache_len,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
        paged=args.paged, block_size=args.block_size))
    nt = args.new_tokens
    workload = poisson_workload(
        0, args.n_requests, cfg.vocab, rate=args.arrival_rate,
        prompt_lens=(2, args.prompt_len),
        budgets=tuple(sorted({max(2, nt // 8), max(2, nt // 2), nt})))
    replay_static(engine, workload, args.n_slots)      # warm both
    replay_continuous(sch, workload)
    rec = compare(replay_static(engine, workload, args.n_slots),
                  replay_continuous(sch, workload))
    print(f"offered load: {args.n_requests} reqs @ "
          f"{args.arrival_rate}/s | weights={args.weights} "
          f"kv_quant={kv_quant} weight_bytes={stored_bytes} "
          f"({stored_bytes / dense_bytes:.2f}x of fp32 dense)")
    for name in ("static", "continuous"):
        m = rec[name]
        print(f"{name:>10}: {m['tok_per_s']:8.1f} tok/s | "
              f"p50 {m['latency_p50_s']:.3f}s p95 {m['latency_p95_s']:.3f}s "
              f"| goodput@SLO {m['goodput_tok_per_s']:8.1f} tok/s | "
              f"{m['decode_launches']} launches")
    print(f"continuous/static throughput: {rec['throughput_ratio']:.2f}x "
          f"(outputs identical: {rec['outputs_identical']})")
    live = {k: v for k, v in sch.counters.items() if v}
    print(f"lifecycle counters: {live}")


def _chaos(cfg, params, args, use_kernel, kv_quant):
    """Seeded chaos replay (DESIGN.md §10): fault-inject the scheduler on
    a deterministic virtual clock and audit the lifecycle invariants
    after every tick."""
    from repro.serve import (Scheduler, SchedulerConfig, ServeConfig,
                             chaos_plan)
    from repro.serve.replay import replay_chaos, sla_workload

    scfg = ServeConfig(weights="fp32", use_kernel=use_kernel,
                       kv_quant=kv_quant, act_fmt=args.act_fmt,
                       max_new_tokens=args.new_tokens)
    cache_len = args.prompt_len + args.new_tokens
    if args.paged and cache_len % args.block_size:
        # blocks tile the ring axis: round up to a whole block
        cache_len += args.block_size - cache_len % args.block_size
    sch = Scheduler(cfg, params, scfg, SchedulerConfig(
        n_slots=args.n_slots, steps_per_tick=args.steps_per_tick,
        cache_len=cache_len, prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache, max_queue=4 * args.n_requests,
        est_tok_per_s=200.0, paged=args.paged,
        block_size=args.block_size))
    wl = sla_workload(args.chaos_seed, args.n_requests, cfg.vocab,
                      rate=args.arrival_rate,
                      prompt_lens=(2, args.prompt_len),
                      budgets=(max(2, args.new_tokens // 2),
                               args.new_tokens))
    plan = chaos_plan(seed=args.chaos_seed, n_ticks=128, vocab=cfg.vocab,
                      cache_len=cache_len)
    print(f"chaos replay: {args.n_requests} reqs + {plan.describe()}")
    res = replay_chaos(sch, wl, plan=plan)
    print(f"terminal states: {res['by_state']} in {res['ticks']} ticks")
    print(f"counters: { {k: v for k, v in res['counters'].items() if v} }")
    print(f"deadline hit rate: {res['deadline_hit_rate']:.2f} | "
          f"goodput {res['goodput_tok']} tok | resume splice "
          f"{res['resume_splice_tokens']}/"
          f"{res['resume_splice_tokens'] + res['resume_recompute_tokens']}"
          f" tokens")
    if res["violations"]:
        for v in res["violations"][:20]:
            print(f"  VIOLATION {v}")
        raise SystemExit(f"{len(res['violations'])} invariant violations")
    print("invariants: 0 violations, all requests terminal")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--weights", default="fp32")
    ap.add_argument("--store", choices=("auto", "qtensor", "dense"),
                    default="auto",
                    help="auto: QTensor codes for int formats, dense cast "
                         "otherwise")
    ap.add_argument("--use-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="wq_matmul dispatch (auto: TPU kernel, else jnp)")
    ap.add_argument("--act-fmt", choices=("int8",), default=None,
                    help="W4A8 serving: row-quantize activations to int8 "
                         "before every quantized weight matmul")
    ap.add_argument("--kv-quant", nargs="?", const="int8", default=None,
                    choices=("int8", "int4"),
                    help="quantized KV cache (bare flag = int8)")
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous-batching offered-load replay "
                         "(vs the static barrier server)")
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--steps-per-tick", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill width: admit long prompts one "
                         "chunk per tick (attention-only patterns)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix KV reuse via the chunk-granular "
                         "radix trie (requires --prefill-chunk)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV: one device-resident block pool shared "
                         "by decode slots and the prefix trie "
                         "(DESIGN.md §13)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per paged-KV pool block (with "
                         "--prefix-cache it must equal --prefill-chunk)")
    ap.add_argument("--n-requests", type=int, default=32)
    ap.add_argument("--arrival-rate", type=float, default=100.0,
                    help="Poisson arrivals per virtual-clock second")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault-injection replay (NaN slots, "
                         "stragglers, eviction storms, malformed/burst "
                         "submissions) with per-tick invariant audit")
    ap.add_argument("--chaos-seed", type=int, default=13)
    return ap.parse_args(argv)


def run(args):
    """Serve as ``args`` says (static prefill+decode, ``--scheduler``
    replay, or ``--chaos`` replay)."""
    mesh = mesh_from_arg(args.mesh)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    dense_bytes = param_nbytes(params)
    if args.weights != "fp32":
        mode, fmt_name = args.weights.split(":")
        fmt = get_format(fmt_name)
        policy = QuantPolicy(min_size=256 if args.smoke else 1024)
        store_q = (args.store == "qtensor"
                   or (args.store == "auto" and isinstance(fmt, IntFormat)
                       and fmt.bits in (4, 8)))
        if store_q:
            params = quantize_params(params, fmt, policy, -1, mode=mode,
                                     key=jax.random.PRNGKey(1))
        else:
            params = cast_params(params, fmt, policy, -1, mode=mode,
                                 key=jax.random.PRNGKey(1))
    use_kernel = {"auto": None, "on": True, "off": False}[args.use_kernel]
    stored_bytes = param_nbytes(params)

    kv_quant = args.kv_quant or False
    cache_len = args.prompt_len + args.new_tokens
    with mesh:
        p_sh = params_shardings(mesh, jax.eval_shape(lambda: params))
        params = jax.device_put(params, p_sh)

        if args.chaos:
            _chaos(cfg, params, args, use_kernel, kv_quant)
            return
        if args.scheduler:
            _replay(cfg, params, args, use_kernel, kv_quant,
                    stored_bytes, dense_bytes)
            return
        toks = jax.random.randint(jax.random.PRNGKey(2),
                                  (args.batch, args.prompt_len), 0, cfg.vocab)

        def prefill_fn(p, t):
            with qtensor_use_kernel(use_kernel), qtensor_act_fmt(args.act_fmt):
                return lm_prefill(p, cfg, t, cache_len=cache_len,
                                  kv_quant=kv_quant)

        def decode_fn(p, c, t, pos):
            with qtensor_use_kernel(use_kernel), qtensor_act_fmt(args.act_fmt):
                return lm_decode(p, cfg, c, t, pos)

        prefill = jax.jit(prefill_fn)
        decode = jax.jit(decode_fn, donate_argnums=(1,))

        t0 = time.perf_counter()
        logits, cache = prefill(params, toks)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        pos = jnp.full((args.batch,), args.prompt_len - 1, jnp.int32)
        t0 = time.perf_counter()
        for _ in range(args.new_tokens):
            pos = pos + 1
            logits, cache = decode(params, cache, tok[:, None], pos)
            tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0

    n_tok = args.batch * args.new_tokens
    print(f"mesh={dict(mesh.shape)} weights={args.weights} "
          f"kv_quant={kv_quant} "
          f"weight_bytes={stored_bytes} ({stored_bytes/dense_bytes:.2f}x "
          f"of fp32 dense)")
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill:.3f}s | "
          f"decode: {n_tok} tokens in {t_decode:.3f}s "
          f"({n_tok/t_decode:.1f} tok/s)")


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
