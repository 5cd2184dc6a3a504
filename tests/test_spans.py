"""The program's marks (``repro.spans``): named scopes in the op metadata
of the compiled train step and serving programs, host spans of
``Scheduler.step``'s phases on a profiler trace, and the compile count."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import QuantConfig
from repro.models.lm import LMConfig, lm_init
from repro.optim import adamw
from repro.serve import Scheduler, SchedulerConfig, ServeConfig
from repro.train import (TrainConfig, init_state, make_optimizer,
                         make_train_step)

CFG = LMConfig(name="sp", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=64, dtype=jnp.float32, remat=False)
CHUNK = 8


def _sched(params, **kw):
    return Scheduler(CFG, params,
                     ServeConfig(max_new_tokens=6, kv_quant="int4",
                                 use_kernel=False),
                     SchedulerConfig(n_slots=2, steps_per_tick=2,
                                     cache_len=32, prefill_chunk=CHUNK,
                                     prefix_cache=True, paged=True,
                                     block_size=CHUNK, **kw))


def _op_names(lowered) -> list:
    """Op metadata names of the compiled program: a loop body's ops carry
    the full path from the program's root there (in the lowered text they
    are relative to the loop)."""
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def _scopes(names) -> set:
    return {seg for n in names for seg in re.split(r"[/()]", n)
            if seg in spans.SCOPES}


def _serving_program(sch, which):
    key = jax.random.PRNGKey(0)
    i32 = jnp.int32
    row = sch._fresh_row()
    if which == "tick":
        return sch._tick.lower(sch.params, sch._pool_cache, sch._tables,
                               sch._state, key)
    if which == "chunk":
        return sch._chunk.lower(sch.params, row, jnp.zeros((1, CHUNK), i32),
                                jnp.zeros((1,), i32),
                                jnp.full((1,), CHUNK, i32), key)
    bps = sch._bps
    return sch._insert_dense_paged.lower(
        sch._pool_cache, sch._state, row, jnp.arange(1, bps + 1, dtype=i32),
        jnp.ones((bps,), bool), 0, 1, 5, 6, -1, 1)


@pytest.mark.parametrize("which,want", [
    ("tick", {spans.EMBED, spans.ATTN, spans.MLP, spans.HEAD,
              spans.KV_CACHE, spans.SAMPLE}),
    ("chunk", {spans.EMBED, spans.ATTN, spans.MLP, spans.HEAD,
               spans.KV_CACHE, spans.SAMPLE}),
    ("insert", {spans.KV_CACHE}),
])
def test_serving_programs_carry_scopes(which, want):
    """The decode tick, the prefill chunk and the paged insert carry the
    program's scopes in their op metadata; the pool's reads and writes
    (the tick's block-table gather and in-place token write, the chunk's
    carry, the insert's scatter, the cache quantizer) are under
    ``kv_cache``.  The paged tick never slices a layer out of the pool
    nor writes one back."""
    sch = _sched(lm_init(jax.random.PRNGKey(0), CFG))
    names = _op_names(_serving_program(sch, which))
    assert want <= _scopes(names), (which, _scopes(names))
    kv_ops = {n.rsplit("/", 1)[-1] for n in names if spans.KV_CACHE in n}
    if which == "tick":
        assert {"gather", "scatter"} <= kv_ops, kv_ops
        assert not {"dynamic_slice", "dynamic_update_slice"} & kv_ops, kv_ops
    if which == "chunk":
        assert {"dynamic_slice", "dynamic_update_slice"} <= kv_ops, kv_ops
    if which == "insert":
        assert "scatter" in kv_ops, kv_ops


def test_train_step_carries_scopes():
    """The train step's gradient is under ``forward_backward`` (backward
    ops carry ``transpose(``) and the optimizer chain under ``update``;
    the layers carry ``embed``/``attn``/``mlp``/``head`` in both passes."""
    tcfg = TrainConfig(quant=QuantConfig(method="lotion", fmt_name="int4",
                                         lam=1.0))
    opt = make_optimizer(tcfg, adamw(lambda count: 1e-3))
    state = init_state(lm_init(jax.random.PRNGKey(0), CFG), opt)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    names = _op_names(jax.jit(make_train_step(CFG, tcfg, opt)).lower(
        state, batch))
    assert {spans.FORWARD_BACKWARD, spans.UPDATE, spans.EMBED, spans.ATTN,
            spans.MLP, spans.HEAD} <= _scopes(names)
    fb = [n for n in names if spans.FORWARD_BACKWARD in _scopes([n])]
    backward = [n for n in fb if "transpose(" in n]
    assert backward and len(backward) < len(fb)
    for scope in (spans.ATTN, spans.MLP):
        assert any(scope in _scopes([n]) for n in backward), scope
    assert not any(spans.FORWARD_BACKWARD in _scopes([n])
                   for n in names if spans.UPDATE in _scopes([n]))



def test_grad_sync_scope_marks_the_gradient_constraint():
    """``grad_sync`` is a program scope; the constraint that moves the
    gradient into the update's layout (``grad_shardings``) is under it and
    under neither ``forward_backward`` nor ``update``.  Without
    ``grad_shardings`` there is no such constraint."""
    from repro.distributed.context import clear_constraints
    from repro.launch.mesh import make_mesh
    from repro.launch.train import shard_setup

    assert spans.GRAD_SYNC == "grad_sync" and spans.GRAD_SYNC in spans.SCOPES
    assert len(set(spans.SCOPES)) == len(spans.SCOPES)
    tcfg = TrainConfig(quant=QuantConfig(method="lotion", fmt_name="int4",
                                         lam=1.0))
    opt = make_optimizer(tcfg, adamw(lambda count: 1e-3))
    state = init_state(lm_init(jax.random.PRNGKey(0), CFG), opt)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    mesh = make_mesh((1, 1), ("data", "model"))
    try:
        with mesh:
            sh = shard_setup(CFG, opt, mesh)
            texts = [jax.jit(make_train_step(CFG, tcfg, opt, grad_shardings=g))
                     .lower(state, batch).as_text(debug_info=True)
                     for g in (sh["params"], None)]
    finally:
        clear_constraints()
    locs = [re.findall(r'loc\("([^"]*)"', t) for t in texts]
    synced = [n for n in locs[0] if spans.GRAD_SYNC in _scopes([n])]
    assert synced and all(n.endswith("sharding_constraint") for n in synced)
    assert not any({spans.FORWARD_BACKWARD, spans.UPDATE} & _scopes([n])
                   for n in synced)
    assert not any(spans.GRAD_SYNC in _scopes([n]) for n in locs[1])


def _host_spans(trace_dir):
    """(name, start, end, stats) of the program's spans in a trace."""
    f = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(f).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_scheduler_spans_on_a_trace(tmp_path):
    """A paged, chunked scheduler traced on the CPU: every ``serve.step``
    holds its phases in order, prefill spans carry ``rid``/``tokens``, and
    each step that ticked holds exactly one ``serve.sync`` after its tick
    dispatch, outside every other phase."""
    sch = _sched(lm_init(jax.random.PRNGKey(0), CFG))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab, size=n).tolist()
               for n in (5, 19, 12, 3)]
    sch.generate(prompts[:1])                       # compile outside
    with jax.profiler.trace(str(tmp_path)):
        rids = [sch.submit(p, 4, arrival=0.0) for p in prompts]
        t = 0.0
        while sch.has_work():
            sch.step(now=t)
            t += 1.0
    got = _host_spans(tmp_path)
    assert [s[3]["rid"] for s in got if s[0] == spans.SERVE_SUBMIT] == rids
    steps = [s for s in got if s[0] == spans.SERVE_STEP]
    assert len(steps) >= len(prompts)
    order = {n: i for i, n in enumerate((
        spans.SERVE_EXPIRE, spans.SERVE_ADMIT, spans.SERVE_PREFILL_CHUNK,
        spans.SERVE_PREFILL_FINISH, spans.SERVE_DECODE_TICK, spans.SERVE_SYNC,
        spans.SERVE_RETIRE))}
    ticked = 0
    for _, s0, e0, _ in steps:
        inside = [s for s in got if s[0] != spans.SERVE_STEP
                  and s0 <= s[1] and s[2] <= e0]
        top = [s for s in inside if not any(
            o is not s and o[1] <= s[1] and s[2] <= o[2] for o in inside)]
        names = [s[0] for s in top]
        assert names[:2] == [spans.SERVE_EXPIRE, spans.SERVE_ADMIT], names
        assert [order[n] for n in names] == sorted(order[n] for n in names)
        if spans.SERVE_DECODE_TICK in names:
            ticked += 1
            assert names.count(spans.SERVE_SYNC) == 1, names
            assert names.index(spans.SERVE_SYNC) == \
                names.index(spans.SERVE_DECODE_TICK) + 1
            assert names[-1] == spans.SERVE_RETIRE
    assert ticked > 0
    chunks = [s[3] for s in got if s[0] == spans.SERVE_PREFILL_CHUNK]
    assert chunks and all({"rid", "tokens", "start"} <= set(c)
                          for c in chunks)
    assert sum(c["tokens"] for c in chunks) == sch.prefill_tokens_computed \
        - len(prompts[0])
    assert {c["rid"] for c in chunks} == set(rids)
    fin = [s for s in got if s[0] == spans.SERVE_PREFILL_FINISH]
    assert sorted(s[3]["rid"] for s in fin) == rids
    admits = [s[3]["n"] for s in got if s[0] == spans.SERVE_ADMIT]
    assert sum(admits) == len(prompts)


def test_compile_count():
    """Each program the process builds counts once; calling it again
    builds nothing."""
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(7)
    n0 = spans.compiles()
    f(x).block_until_ready()
    n1 = spans.compiles()
    f(x).block_until_ready()
    assert n1 == n0 + 1 and spans.compiles() == n1
