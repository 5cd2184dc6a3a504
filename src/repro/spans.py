"""The program's marks on a profiler trace, and a count of its compiles.

Host spans.  :func:`span` returns a ``jax.profiler.TraceAnnotation`` and
:func:`step` a ``jax.profiler.StepTraceAnnotation``.  While a profiler
trace is active (``jax.profiler.trace(dir)``; ``--profile DIR`` on the
launchers) each lands on the trace's host plane, on the same clock as the
device planes, with its keyword arguments as stats; while none is, a span
costs the creation of one object.  A span of one request carries its
``rid``; a span of a batch carries counts.  A count known only at the end
of a span is added with ``set_metadata`` on the object ``with`` yields.

Device scopes.  The model, the train step and the sampler wrap their ops
in ``jax.named_scope`` with the names below, so every op of a compiled
program carries them in its metadata (``op_name``) and on a TPU trace.
A scope changes op metadata only, never numerics or fusion.  Backward ops
carry ``transpose(`` in their name stack, so ``forward_backward`` splits
into forward and backward without a scope of its own for each.

The benchmark's trace reader (``bench/harness/program_trace.py``) reads
these names from here.
"""

from __future__ import annotations

import threading

import jax

# ---- host spans: serving (serve/scheduler.py) ----------------------------
SERVE_SUBMIT = "serve.submit"                 # rid
SERVE_STEP = "serve.step"                     # one Scheduler.step call
SERVE_EXPIRE = "serve.expire"                 # deadline enforcement
SERVE_ADMIT = "serve.admit"                   # n: requests admitted
SERVE_PREFILL_CHUNK = "serve.prefill_chunk"   # rid, tokens, start
SERVE_PREFILL_FINISH = "serve.prefill_finish"  # rid: publish + insert
SERVE_DECODE_TICK = "serve.decode_tick"       # slots: the tick's dispatch
SERVE_SYNC = "serve.sync"                     # a blocking device read
SERVE_RETIRE = "serve.retire"                 # host loop over the tick
SERVE_QUARANTINE = "serve.quarantine"         # rid: non-finite fallback

# ---- host spans: training (train/loop.py::run_loop) ----------------------
TRAIN_STEP = "train.step"                     # step marker, step_num
TRAIN_DATA = "train.data"                     # next(pipeline)
TRAIN_DISPATCH = "train.dispatch"             # the jitted step's dispatch
TRAIN_SYNC = "train.sync"                     # the loss read: the step sync
TRAIN_EVAL = "train.eval"
TRAIN_CKPT = "train.ckpt"
TRAIN_ROLLBACK = "train.rollback"

# ---- device scopes (jax.named_scope) -------------------------------------
EMBED = "embed"
ATTN = "attn"
MLP = "mlp"
HEAD = "head"
KV_CACHE = "kv_cache"          # every read and write of a stacked/pooled cache
FORWARD_BACKWARD = "forward_backward"
GRAD_SYNC = "grad_sync"        # the gradient's move into the update's layout
UPDATE = "update"              # the optimizer chain and its apply
SAMPLE = "sample"
SCOPES = (EMBED, ATTN, MLP, HEAD, KV_CACHE, FORWARD_BACKWARD, GRAD_SYNC,
          UPDATE, SAMPLE)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``ids`` (a request's ``rid``, a
    batch's counts) as stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


def step(name: str, n: int) -> jax.profiler.StepTraceAnnotation:
    """A step marker: the profiler groups a trace's work by these."""
    return jax.profiler.StepTraceAnnotation(name, step_num=n)


# ---- compiles ------------------------------------------------------------
# JAX times every executable build, from the compiler or the persistent
# cache, under this event; builds can come from several threads (the data
# pipeline's prefetch compiles too)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_compiles_lock = threading.Lock()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    global _compiles
    if event == _BACKEND_COMPILE:
        with _compiles_lock:
            _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> int:
    """Programs built for the backend in this process since this module
    was imported: each compiled, or loaded from the persistent compile
    cache.  A steady loop builds none."""
    return _compiles
