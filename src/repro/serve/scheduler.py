"""Continuous-batching scheduler: persistent decode slots + on-device
multi-step decode, with a fault-tolerant request lifecycle.

The static :class:`~repro.serve.engine.Engine` barrier-synchronizes one
batch per ``generate`` call: every request pays the batch-max prompt
width, the batch-max token budget, and one host->device dispatch per
token.  Quantized storage (DESIGN.md §6) made each decode step
weight-cheap, but a Python-dispatched step per token means the int4
bandwidth win never becomes throughput.  The scheduler turns the decode
loop inside out:

* **Fixed slot pool** — the decode batch dim is a compile-time constant
  (``n_slots``), so the hot loop compiles ONCE regardless of load; free
  slots ride along masked instead of forcing a re-jit at every occupancy
  change.
* **Per-slot prefill-insert admission** — a queued request prefills alone
  (batch=1, its own length — no batchmate padding) against the pool's
  ``cache_len``; the resulting cache row is spliced into the pool at its
  slot by :func:`~repro.models.lm.cache_insert`, replacing the previous
  occupant's row wholesale (slot reuse cannot leak KV).
* **k-step on-device decode tick** — one ``lax.scan`` advances EVERY
  active slot ``steps_per_tick`` tokens: sampling, cache ring-writes and
  per-slot done-masking (token budget / EOS) all run inside the scan, so
  a request costs ceil((mnt-1)/k) decode dispatches instead of mnt-1.
  Finished and free slots stop advancing (frozen position, re-writing the
  same KV — idempotent) and are masked out of MoE capacity via
  ``token_mask``.
* **Retirement + admission** — after each tick the host reads the
  (k, n_slots) emitted-token block (one transfer), applies the SAME
  termination rule the device used, releases finished slots, and admits
  queued requests highest-priority-first (submit order within a priority
  class, lowest free slot first — a replayed request stream is
  deterministic).

* **Chunked prefill** (``prefill_chunk``, DESIGN.md §8) — a long prompt
  no longer stalls the tick it is admitted in: the request takes a slot
  in state PREFILLING and its prompt advances ONE fixed-width chunk per
  tick (``lm_prefill_chunk`` resumes positions against the request's
  dense partial cache), interleaved with the decode scan — so the
  prefill work any tick can impose on decoding requests is bounded by
  the chunk width, not the longest prompt in the queue.
* **Prefix-cache sharing** (``prefix_cache``) — whole-chunk prompt-
  prefix hits against a refcounted LRU radix trie are spliced into the
  partial cache as plain row copies, skipping the shared prefix's
  prefill FLOPs entirely (exact-match token-ID keys + deterministic
  chunked prefill keep greedy outputs token-identical).

Fault tolerance (DESIGN.md §10) — every submitted request terminally
resolves; overload degrades instead of collapsing:

* **Lifecycle enforcement** — requests move through the explicit state
  machine in :mod:`repro.serve.slots`; illegal edges raise, and the
  chaos harness (:mod:`repro.serve.faults`) audits global invariants
  (no slot leak, no pin leak, all-terminal at drain) after every tick.
* **Deadlines** — an expired request is timed out at admission, mid-
  prefill (its trie pins released — the pin-leak fix), or mid-decode
  (its slot is done-masked out of the tick scan and freed).
* **Priority preemption** — a higher-priority arrival evicts the lowest-
  priority PREFILLING/DECODING slot back to the queue (strictly-lower
  priority only, so preemption cannot livelock).  The victim's computed
  KV chunks are published to the prefix trie first (always exact for
  PREFILLING partial caches, which stay dense; for DECODING rows when
  the pool KV is dense), so its later resume — a chunked re-prefill of
  ``prompt + out[:-1]`` — is mostly trie splices: preemption cost is a
  measured number (``resume_splice_tokens``), not a vibe.
* **SLO-aware admission** — the queue is bounded (``max_queue``), and a
  deadlined request whose estimated queue-wait + service time already
  overruns its deadline is shed at submit with a typed reason instead of
  queueing forever.
* **Non-finite quarantine** — the decode scan done-masks any slot whose
  logits go non-finite (int4 weights + int8 activations make this a real
  fault class) and reports a per-(step, slot) poison mask alongside the
  emitted tokens; the host quarantines the slot and retries the request
  ONCE on the jnp fallback path (``use_kernel=False`` engine) — kernel
  bugs degrade to slow-but-correct.  FAILED only if the fallback also
  faults.

Each ``submit`` and ``step``, and each phase of a step (expiry,
admission, the prefill chunk, its finish, the tick dispatch, the sync on
its tokens, retirement), is a host span of :mod:`repro.spans`, so a
profiler trace puts the device's idle time down to what the host was
doing; the jitted programs' ops carry the model's named scopes.

Greedy generations are token-identical to the static engine for the same
request set (the engine's per-row ``prompt_lens`` masking makes static
batching pad-invariant; capacity-based MoE routing is the documented
exception — expert-capacity contention is inherently batch-composition-
dependent).  Temperature sampling uses per-request/per-tick folded keys
and is NOT stream-identical to the static engine.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Set, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.qtensor import qtensor_act_fmt, qtensor_use_kernel
from repro.models.lm import (LMConfig, cache_insert, cache_insert_paged,
                             init_cache, init_paged_pool, lm_decode,
                             lm_prefill, lm_prefill_chunk, quantize_cache)

from .block_pool import BlockPool
from .engine import (Engine, ServeConfig, attn_only, bucket_cache_len,
                     full_ring, prepare_params, sample_token)
from .prefix_cache import PrefixCache
from .slots import (COMPLETED, DECODING, FAILED, PREEMPTED, PREFILLING,
                    QUEUED, REJECTED, TIMED_OUT, RejectedError, Request,
                    SlotPool, request_problem)

COUNTER_KEYS = ("submitted", "admitted", "completed", "timed_out",
                "rejected", "shed", "preempted", "resumed", "failed",
                "nan_events", "nan_retries")


def decode_tick(cfg: LMConfig, scfg: ServeConfig, k: int, p, cache,
                state, key, tables=None):
    """One scheduler tick: ``k`` decode steps of every slot in one
    program.  ``cache`` is the slot-pool cache, or with ``tables`` (the
    slots' block tables) the paged pool.  Returns (cache, state, emitted,
    bad), the last two ``(k, n_slots)``: each step's sampled token (-1
    where the slot emitted none) and its non-finite mask."""
    mnt, eos = state["mnt"], state["eos"]

    def body(carry, kk):
        cache, tok, pos, steps, active = carry
        pos2 = jnp.where(active, pos + 1, pos)
        with qtensor_use_kernel(scfg.use_kernel), \
                qtensor_act_fmt(scfg.act_fmt):
            logits, cache = lm_decode(p, cfg, cache, tok[:, None], pos2,
                                      token_mask=active,
                                      block_tables=tables)
        # non-finite guard (DESIGN.md §10): a poisoned slot is
        # done-masked INSIDE the scan — it stops sampling, stops
        # writing KV, and emits nothing from the bad step on; the
        # (k, n_slots) poison mask rides the existing per-tick
        # transfer so the guard costs one reduction, not a sync
        ok = jnp.isfinite(logits[:, 0]).all(axis=-1)
        bad = active & ~ok
        live = active & ok
        with jax.named_scope(spans.SAMPLE):
            sampled = sample_token(logits[:, 0], kk, scfg.temperature)
        new_tok = jnp.where(live, sampled, tok).astype(jnp.int32)
        steps2 = jnp.where(live, steps + 1, steps)
        emitted = jnp.where(live, new_tok, -1)
        done = (steps2 >= mnt) | (new_tok == eos) | bad
        return ((cache, new_tok, pos2, steps2, active & ~done),
                (emitted, bad))

    keys = jax.random.split(key, k)
    carry = (cache, state["tok"], state["pos"], state["steps"],
             state["active"])
    (cache, tok, pos, steps, active), (em, bad) = jax.lax.scan(
        body, carry, keys)
    new_state = {"tok": tok, "pos": pos, "steps": steps,
                 "mnt": mnt, "eos": eos, "active": active}
    return cache, new_state, em, bad


@dataclasses.dataclass
class SchedulerConfig:
    n_slots: int = 8            # decode batch dim (compile-time constant)
    steps_per_tick: int = 4     # k: tokens decoded per host->device launch
    cache_len: int = 256        # per-slot KV capacity (prompt + generation)
    # pow2-bucket per-request prefill widths (attention-only patterns,
    # where pad masking makes it output-invariant): bounds prefill re-jits
    # to O(log cache_len) instead of one per distinct prompt length
    bucket_prompts: bool = True
    # chunked prefill (DESIGN.md §8): admit a prompt across ticks in
    # fixed-size chunks — one chunk per tick interleaved with the decode
    # scan, so decode stall per tick is bounded by the chunk width, not
    # the longest prompt.  None = monolithic prefill-insert (PR 4).
    # Attention-only patterns (see serve.engine.attn_only).
    prefill_chunk: Optional[int] = None
    # shared-prefix KV reuse: splice whole-chunk prefix hits from a
    # refcounted LRU radix trie instead of re-prefilling them (requires
    # prefill_chunk; exact-match, so greedy outputs are unchanged)
    prefix_cache: bool = False
    prefix_cache_blocks: int = 256   # LRU capacity, in prefill_chunk blocks
    # ---- fault tolerance / SLO knobs (DESIGN.md §10) ----
    # bounded submit queue: a submission past this depth is REJECTED
    # ("queue_full") instead of queueing without bound
    max_queue: int = 4096
    # priority preemption: a strictly-higher-priority arrival may evict
    # the lowest-priority running slot back to the queue
    preempt: bool = True
    # SLO-aware load shedding: shed a deadlined submission whose
    # estimated wait + service already overruns its deadline
    slo_shed: bool = True
    # service-rate estimate (tokens per virtual-clock second) for the
    # shed decision; None = learn an EMA from observed step() progress
    # (no shedding until the first estimate exists)
    est_tok_per_s: Optional[float] = None
    # ---- paged KV (DESIGN.md §13) ----
    # device-resident block pool shared by decode slots and the prefix
    # trie: each slot's KV lives in cache_len//block_size pool blocks
    # addressed through a per-slot block table, so prefix reuse is a
    # table append (zero-copy) and preempted DECODING victims keep their
    # quantized blocks pinned for an exact zero-recompute reattach
    paged: bool = False
    block_size: int = 16        # tokens per pool block (ring-axis granule)
    # pool capacity in blocks; None = n_slots contexts + the prefix-trie
    # capacity (when enabled) + the reserved null block
    pool_blocks: Optional[int] = None


@dataclasses.dataclass
class _PagedBlock:
    """Prefix-trie payload in paged mode: the dense device-resident
    chunk (``shadow`` — spliced into partial prefill caches with a
    device DUS, no host round-trip) plus, once a producer attaches one,
    the pinned pool block holding the chunk's serving-format bytes
    (``block_id`` — a consumer shares it by appending the id to its
    block table).  PREFILLING victims publish shadow-only payloads;
    the first completed consumer upgrades ``block_id`` in place."""

    shadow: Any
    block_id: Optional[int] = None


@dataclasses.dataclass
class _PrefillJob:
    """Host-side progress of one chunked prompt admission."""

    rid: int
    seq: List[int]               # tokens to prefill (resume: prompt+out[:-1])
    cache: Any                   # dense partial cache, batch=1 (device)
    next: int                    # next seq index to prefill
    pinned: list                 # prefix-trie nodes pinned by the lookup


class Scheduler:
    """Continuous-batching server over a fixed pool of decode slots."""

    def __init__(self, cfg: LMConfig, params, scfg: Optional[ServeConfig]
                 = None, sched: Optional[SchedulerConfig] = None):
        self.cfg = cfg
        self.scfg = scfg = scfg if scfg is not None else ServeConfig()
        self.sched = sched = sched if sched is not None else SchedulerConfig()
        self.params = prepare_params(params, scfg)
        self.pool = SlotPool(sched.n_slots)
        self.requests: Dict[int, Request] = {}
        self.queue: collections.deque = collections.deque()
        self._next_rid = 0
        self._admit_seq = 0
        self._mask_pads = attn_only(cfg)
        self._key = jax.random.PRNGKey(scfg.seed + 1)
        self._tick_key = jax.random.PRNGKey(scfg.seed + 2)
        # structural dispatch accounting (ISSUE 4 acceptance)
        self.n_ticks = 0
        self.n_prefills = 0
        # lifecycle counters (ISSUE 7): the replay harness and launch
        # logging read these; faults.py checks they balance at drain
        self.counters: Dict[str, int] = {k: 0 for k in COUNTER_KEYS}
        # fault-injection hooks (serve/faults.py): slots to treat as
        # non-finite at the next tick, and rids whose one fallback retry
        # must also fault (simulating a fallback-path numeric fault)
        self._inject_bad_slots: Set[int] = set()
        self._fail_fallback_rids: Set[int] = set()
        self._fallback: Optional[Engine] = None
        # learned service-rate EMA for SLO shedding (virtual-clock based)
        self._ema_tok_per_s: Optional[float] = None
        self._last_now: Optional[float] = None
        self._emitted_tokens = 0
        self._emitted_at_last_now = 0
        # preemption-resume accounting: tokens a resume re-prefill
        # spliced from the trie vs recomputed (the preemption cost)
        self.resume_splice_tokens = 0
        self.resume_recompute_tokens = 0
        # paged-KV structural counters (DESIGN.md §13), defined in every
        # mode so benches can report them unconditionally: host<->device
        # transfers spent assembling/publishing prefix splices (the
        # legacy row-copy path; 0 in paged mode — a gated bench column)
        # and pool blocks shared via table appends on prefix hits
        self.splice_host_transfers = 0
        self.prefix_blocks_shared = 0
        # chunked-prefill / prefix-cache accounting (ISSUE 5): prefill
        # tokens computed (its per-step delta is the decode-stall signal
        # — bounded by prefill_chunk when chunking is on, by the longest
        # prompt when it is not) and tokens skipped via prefix-cache
        # splices
        self.prefill_tokens_computed = 0
        self.prefill_tokens_skipped = 0

        self._chunked = sched.prefill_chunk is not None
        if self._chunked:
            if sched.prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {sched.prefill_chunk}")
            if not self._mask_pads or "xattn" in cfg.pattern:
                raise ValueError(
                    f"chunked prefill requires a self-attention-only "
                    f"dense-FFN pattern (recurrent blocks do not thread "
                    f"state across chunks; capacity-based MoE routing is "
                    f"chunk-dependent; xattn has no encoder context on "
                    f"the serving path); {cfg.name} has "
                    f"pattern={cfg.pattern}, ffn={cfg.ffn}")
        n, k, cl = sched.n_slots, sched.steps_per_tick, sched.cache_len
        dt = cfg.dtype

        self.paged = sched.paged
        self.block_pool: Optional[BlockPool] = None
        if self.paged:
            bs = sched.block_size
            if bs < 1:
                raise ValueError(f"block_size must be >= 1, got {bs}")
            if cl % bs:
                raise ValueError(
                    f"cache_len {cl} must be a multiple of "
                    f"block_size {bs} (blocks tile the ring axis)")
            reason = full_ring(cfg, cl)
            if reason is not None:
                raise ValueError(
                    f"paged KV needs every layer's ring to cover "
                    f"cache_len (slot == position, so one block table "
                    f"addresses every layer's pool); {reason}")
            if sched.prefix_cache and bs != sched.prefill_chunk:
                raise ValueError(
                    f"paged + prefix_cache requires block_size == "
                    f"prefill_chunk (a trie node IS one pool block), "
                    f"got {bs} vs {sched.prefill_chunk}")
            self._bps = cl // bs          # blocks per slot table
            nb = sched.pool_blocks if sched.pool_blocks is not None else (
                n * self._bps
                + (sched.prefix_cache_blocks if sched.prefix_cache else 0)
                + 1)
            if nb < self._bps + 1:
                raise ValueError(
                    f"pool_blocks={nb} cannot hold one context "
                    f"({self._bps} blocks + the null block)")
            self.block_pool = BlockPool(nb)
            self._pool_cache = init_paged_pool(cfg, nb, bs, dtype=dt,
                                               kv_quant=scfg.kv_quant)
            # host mirror is authoritative; the device copy refreshes
            # lazily before a tick when any row changed
            self._tables_host = np.zeros((n, self._bps), np.int32)
            self._tables = jnp.asarray(self._tables_host)
            self._tables_dirty = False
            # rids of chunked paged jobs whose blocks are not allocated
            # yet (alloc happens at the final-chunk insert) — admission
            # holds back _bps free blocks for each
            self._paged_reserved: Set[int] = set()

        self.prefix: Optional[PrefixCache] = None
        if sched.prefix_cache:
            if not self._chunked:
                raise ValueError("prefix_cache requires prefill_chunk "
                                 "(blocks are chunk-granular)")
            reason = full_ring(cfg, cl)
            if reason is not None:
                raise ValueError(
                    f"prefix_cache needs every layer's ring to cover "
                    f"cache_len (slot == position, so prefix blocks "
                    f"are extractable); {reason}")
            self.prefix = PrefixCache(
                sched.prefill_chunk, sched.prefix_cache_blocks,
                on_evict=self._on_trie_evict if self.paged else None)
        self._prefills: Dict[int, _PrefillJob] = {}
        self._prefill_q: collections.deque = collections.deque()

        self._cache = (None if self.paged else
                       init_cache(cfg, n, cl, dtype=dt,
                                  kv_quant=scfg.kv_quant))
        self._state = {
            "tok": jnp.zeros((n,), jnp.int32),
            "pos": jnp.zeros((n,), jnp.int32),
            "steps": jnp.zeros((n,), jnp.int32),
            "mnt": jnp.zeros((n,), jnp.int32),
            "eos": jnp.full((n,), -1, jnp.int32),
            "active": jnp.zeros((n,), bool),
        }

        def _sample(logits, key):
            with jax.named_scope(spans.SAMPLE):
                return sample_token(logits, key, scfg.temperature)

        tick = functools.partial(decode_tick, cfg, scfg, k)

        def _prefill_fn(p, toks, lens, key):
            with qtensor_use_kernel(scfg.use_kernel), \
                    qtensor_act_fmt(scfg.act_fmt):
                logits, row_cache = lm_prefill(
                    p, cfg, toks, cache_len=cl, kv_quant=scfg.kv_quant,
                    prompt_lens=lens)
            return _sample(logits[:, 0], key), row_cache

        def _insert_fn(cache, state, row_cache, slot, tok, plen, mnt, eos,
                       steps):
            # ``steps`` is the tokens already emitted (1 on a fresh
            # admission; len(out) on a preemption resume, so the device
            # budget rule ``steps >= mnt`` stays aligned with the host's)
            cache = cache_insert(cache, row_cache, slot)
            state = {
                "tok": state["tok"].at[slot].set(tok),
                "pos": state["pos"].at[slot].set(plen - 1),
                "steps": state["steps"].at[slot].set(steps),
                "mnt": state["mnt"].at[slot].set(mnt),
                "eos": state["eos"].at[slot].set(eos),
                "active": state["active"].at[slot].set(True),
            }
            return cache, state

        def _chunk_fn(p, row_cache, toks, start, lens, key):
            with qtensor_use_kernel(scfg.use_kernel), \
                    qtensor_act_fmt(scfg.act_fmt):
                logits, row_cache = lm_prefill_chunk(p, cfg, row_cache,
                                                     toks, start, lens)
            return _sample(logits[:, 0], key), row_cache

        def _insert_dense_fn(cache, state, row_cache, slot, tok, plen,
                             mnt, eos, steps):
            # chunked partial caches stay dense until this insert (chunk
            # attention must read earlier chunks at monolithic precision)
            row_cache = quantize_cache(cfg, row_cache, scfg.kv_quant)
            return _insert_fn(cache, state, row_cache, slot, tok, plen,
                              mnt, eos, steps)

        def _set_state(state, slot, tok, pos, mnt, eos, steps):
            return {
                "tok": state["tok"].at[slot].set(tok),
                "pos": state["pos"].at[slot].set(pos),
                "steps": state["steps"].at[slot].set(steps),
                "mnt": state["mnt"].at[slot].set(mnt),
                "eos": state["eos"].at[slot].set(eos),
                "active": state["active"].at[slot].set(True),
            }

        def _insert_paged_fn(pool, state, row_cache, table, write, slot,
                             tok, plen, mnt, eos, steps):
            # scatter the (already serving-format) batch=1 row into this
            # slot's pool blocks; chunks with write=False came from the
            # trie and already hold the exact bytes (shared blocks are
            # redirected to the never-read null block)
            pool = cache_insert_paged(pool, row_cache, table, write)
            return pool, _set_state(state, slot, tok, plen - 1, mnt, eos,
                                    steps)

        def _insert_dense_paged_fn(pool, state, row_cache, table, write,
                                   slot, tok, plen, mnt, eos, steps):
            row_cache = quantize_cache(cfg, row_cache, scfg.kv_quant)
            return _insert_paged_fn(pool, state, row_cache, table, write,
                                    slot, tok, plen, mnt, eos, steps)

        def _reattach_fn(state, slot, tok, pos, mnt, eos, steps):
            # preemption resume by table re-attach: the victim's pool
            # blocks were never freed, so only the scalar decode state
            # needs restoring — zero recompute, exact for any KV format
            return _set_state(state, slot, tok, pos, mnt, eos, steps)

        self._prefill = jax.jit(_prefill_fn)
        if self.paged:
            self._insert_paged = jax.jit(_insert_paged_fn,
                                         donate_argnums=(0, 1))
            self._reattach = jax.jit(_reattach_fn, donate_argnums=(0,))
            # (params, pool, tables, state, key)
            self._tick = jax.jit(
                lambda p, pool, tables, state, key: tick(p, pool, state,
                                                         key, tables),
                donate_argnums=(1, 3))
        else:
            self._insert = jax.jit(_insert_fn, donate_argnums=(0, 1))
            self._tick = jax.jit(tick, donate_argnums=(1, 2))
        if self._chunked:
            self._chunk = jax.jit(_chunk_fn, donate_argnums=(1,))
            if self.paged:
                self._insert_dense_paged = jax.jit(_insert_dense_paged_fn,
                                                   donate_argnums=(0, 1))
            else:
                self._insert_dense = jax.jit(_insert_dense_fn,
                                             donate_argnums=(0, 1))
            # fresh partial caches: device-side zeros (no host upload on
            # the common prefix-miss admission)
            self._fresh_row = jax.jit(
                lambda: init_cache(cfg, 1, cl, dtype=dt, kv_quant=False))
            # host-side zero template for prefix-spliced partial caches
            shapes = jax.eval_shape(self._fresh_row)
            self._row_template = jax.tree.map(
                lambda s: np.zeros(s.shape, s.dtype), shapes)

    # ------------------------------------------------------------------
    # request API
    # ------------------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None,
               arrival: float = 0.0,
               deadline: Optional[float] = None,
               priority: int = 0,
               strict: bool = True) -> int:
        """Queue one request; returns its request id.  Admission happens
        on subsequent :meth:`step` calls, highest priority first (submit
        order within a class).

        Admission control runs HERE, not deep inside prefill: malformed
        prompts (empty / out-of-vocab / over ``cache_len``) raise a typed
        :class:`RejectedError` (``strict=False`` records a REJECTED
        terminal request instead), a full queue rejects with
        ``"queue_full"``, and a deadline that the current backlog already
        makes unmeetable is shed with ``"slo_shed"`` (``slo_shed=True``).
        """
        # every path that returns a rid returns this one
        with spans.span(spans.SERVE_SUBMIT, rid=self._next_rid):
            mnt = (max_new_tokens if max_new_tokens is not None
                   else self.scfg.max_new_tokens)
            self.counters["submitted"] += 1
            problem = request_problem(prompt, mnt, self.sched.cache_len,
                                      self.cfg.vocab)
            if problem is not None:
                reason, msg = problem
                if strict:
                    # the submission never happened: raise without recording
                    self.counters["submitted"] -= 1
                    raise RejectedError(reason, msg)
                self.counters["rejected"] += 1
                return self._terminal_submission(prompt, mnt, eos_id, arrival,
                                                 REJECTED, reason)
            if len(self.queue) >= self.sched.max_queue:
                # bounded queue: shed at the door instead of queueing forever
                if strict:
                    self.counters["submitted"] -= 1
                    raise RejectedError(
                        "queue_full",
                        f"submit queue at max_queue={self.sched.max_queue}")
                self.counters["rejected"] += 1
                return self._terminal_submission(prompt, mnt, eos_id, arrival,
                                                 REJECTED, "queue_full")
            if (deadline is not None and self.sched.slo_shed
                    and self._deadline_unmeetable(prompt, mnt, arrival,
                                                  deadline)):
                self.counters["shed"] += 1
                return self._terminal_submission(prompt, mnt, eos_id, arrival,
                                                 REJECTED, "slo_shed")
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid=rid, prompt=list(prompt), max_new_tokens=mnt,
                          eos_id=eos_id, arrival=arrival, deadline=deadline,
                          priority=priority)
            self.requests[rid] = req
            if mnt <= 0:
                req.transition(COMPLETED, "empty_budget")
                self.counters["completed"] += 1
            else:
                self.queue.append(rid)
            return rid

    def _terminal_submission(self, prompt, mnt, eos_id, arrival,
                             state: str, reason: str) -> int:
        """Record a request that terminates at the door (still tracked,
        so accounting sees every submission exactly once)."""
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=list(prompt), max_new_tokens=mnt,
                      eos_id=eos_id, arrival=arrival)
        req.transition(state, reason)
        self.requests[rid] = req
        return rid

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.pool.occupied())

    def step(self, now: Optional[float] = None) -> List[Request]:
        """Enforce deadlines (when ``now`` is given), admit what fits
        (arrival-gated, priority-first, preempting if configured),
        advance at most one prefill chunk (chunked mode), run one decode
        tick, retire finished slots.  Returns every request that reached
        a terminal state during this step."""
        with spans.span(spans.SERVE_STEP):
            self._update_rate_estimate(now)
            terminal = []
            if now is not None:
                with spans.span(spans.SERVE_EXPIRE):
                    terminal += self._expire(now)
            with spans.span(spans.SERVE_ADMIT) as sp:
                admitted = self.counters["admitted"]
                terminal += self._admit(now)
                sp.set_metadata(n=self.counters["admitted"] - admitted)
            if self._chunked:
                terminal += self._prefill_tick()
            terminal += self._do_tick()
        return terminal

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue and all active slots; returns {rid: tokens}
        for COMPLETED requests."""
        while self.has_work():
            self.step()
        return {rid: r.out for rid, r in self.requests.items() if r.done}

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Union[int, Sequence[int], None] = None,
                 eos_id: Union[int, Sequence[int], None] = None,
                 ) -> List[List[int]]:
        """Engine-compatible convenience: submit a batch, drain, return
        outputs in submission order."""
        from .engine import _per_request
        b = len(prompts)
        mnts = _per_request(max_new_tokens, self.scfg.max_new_tokens, b)
        eoss = _per_request(eos_id, None, b)
        rids = [self.submit(p, m, e) for p, m, e in zip(prompts, mnts, eoss)]
        self.run()
        return [self.requests[r].out for r in rids]

    # ------------------------------------------------------------------
    # fault-injection hooks (serve/faults.py drives these)
    # ------------------------------------------------------------------

    def inject_nonfinite(self, slots: Sequence[int],
                         fail_fallback: bool = False) -> None:
        """Treat ``slots`` as if their next tick produced non-finite
        logits (deterministic stand-in for a real kernel fault: the host
        quarantine path is identical).  ``fail_fallback`` makes the
        quarantined requests' one fallback retry fault too -> FAILED."""
        self._inject_bad_slots.update(int(s) for s in slots)
        if fail_fallback:
            for s in slots:
                rid = dict(self.pool.occupied()).get(int(s))
                if rid is not None:
                    self._fail_fallback_rids.add(rid)

    def _fallback_engine(self) -> Engine:
        """Lazily-built jnp-reference engine (``use_kernel=False``) over
        the SAME prepared params: the slow-but-correct retry path for
        quarantined requests.  ``weights="fp32"`` makes prepare_params a
        no-op — the params are already in serving representation."""
        if self._fallback is None:
            fcfg = dataclasses.replace(self.scfg, weights="fp32",
                                       use_kernel=False)
            self._fallback = Engine(self.cfg, self.params, fcfg)
        return self._fallback

    # ------------------------------------------------------------------
    # SLO admission control
    # ------------------------------------------------------------------

    def _update_rate_estimate(self, now: Optional[float]) -> None:
        if now is None:
            return
        if self._last_now is not None and now > self._last_now:
            emitted = self._emitted_tokens - self._emitted_at_last_now
            if emitted > 0:
                inst = emitted / (now - self._last_now)
                ema = self._ema_tok_per_s
                self._ema_tok_per_s = (inst if ema is None
                                       else 0.8 * ema + 0.2 * inst)
        self._last_now = now
        self._emitted_at_last_now = self._emitted_tokens

    def _service_rate(self) -> Optional[float]:
        return self.sched.est_tok_per_s or self._ema_tok_per_s

    def _backlog_tokens(self) -> int:
        """Tokens of work ahead of a new arrival: queued prompts+budgets
        plus the unfinished remainder of every running slot."""
        total = 0
        for rid in self.queue:
            r = self.requests[rid]
            total += len(r.resume_tokens()) + r.max_new_tokens - len(r.out)
        for _, rid in self.pool.occupied():
            r = self.requests[rid]
            if r.state == PREFILLING:
                job = self._prefills.get(rid)
                left = (len(job.seq) - job.next) if job is not None else \
                    len(r.resume_tokens())
                total += left + r.max_new_tokens - len(r.out)
            elif r.state == DECODING:
                total += r.max_new_tokens - len(r.out)
        return total

    def _deadline_unmeetable(self, prompt, mnt: int, arrival: float,
                             deadline: float) -> bool:
        """Shed decision: estimated wait for the backlog + this request's
        own service time vs the slack it arrived with.  No service-rate
        estimate yet (cold start, no est_tok_per_s) => never shed."""
        rate = self._service_rate()
        if not rate or rate <= 0:
            return False
        est = (self._backlog_tokens() + len(prompt) + mnt) / rate
        return arrival + est > deadline

    # ------------------------------------------------------------------
    # deadline enforcement (admission, mid-prefill, mid-decode)
    # ------------------------------------------------------------------

    def _expire(self, now: float) -> List[Request]:
        expired = []
        for rid in [r for r in self.queue
                    if self._past_deadline(r, now)]:
            req = self.requests[rid]
            self.queue.remove(rid)
            if self.paged and req.blocks is not None:
                self._free_req_blocks(req)   # preempted victim's table
            req.transition(TIMED_OUT, "deadline_queued")
            self.counters["timed_out"] += 1
            expired.append(req)
        for slot, rid in list(self.pool.occupied()):
            req = self.requests[rid]
            if not self._past_deadline(rid, now):
                continue
            if req.state == PREFILLING:
                self._cancel_prefill_job(rid)     # releases trie pins
            elif req.state == DECODING:
                self._deactivate_slot(slot)       # done-mask out of tick
                self._release_slot_blocks(slot)
            self.pool.release(slot)
            req.slot = None
            req.transition(TIMED_OUT, "deadline_" + (
                "prefill" if req.state == PREFILLING else "decode"))
            self.counters["timed_out"] += 1
            expired.append(req)
        return expired

    def _past_deadline(self, rid: int, now: float) -> bool:
        d = self.requests[rid].deadline
        return d is not None and now >= d

    def _deactivate_slot(self, slot: int) -> None:
        """Done-mask a slot out of the decode scan (its device row stops
        advancing; the next insert replaces the row wholesale)."""
        self._state = dict(self._state,
                           active=self._state["active"].at[slot].set(False))

    def _cancel_prefill_job(self, rid: int) -> None:
        """Tear down an in-flight chunked prefill WITHOUT leaking its
        trie pins (the pin-leak fix: a request dying between
        ``_start_prefill`` and completion must release its pinned path)."""
        job = self._prefills.pop(rid, None)
        if job is None:
            return
        if self.paged:
            self._paged_reserved.discard(rid)
        self._prefill_q.remove(rid)
        if self.prefix is not None and job.pinned:
            self.prefix.release(job.pinned)

    # ------------------------------------------------------------------
    # priority preemption
    # ------------------------------------------------------------------

    def _next_admittable(self, now: Optional[float]) -> Optional[int]:
        """Highest-priority arrived request; submit order (lowest rid)
        within a class — with uniform priorities this IS the legacy FIFO
        order, so pre-lifecycle replays are bit-identical."""
        best = None
        for rid in self.queue:
            req = self.requests[rid]
            if now is not None and req.arrival > now:
                continue
            if best is None or (req.priority, -rid) > \
                    (self.requests[best].priority, -best):
                best = rid
        return best

    def _preempt_for(self, incoming: Request) -> bool:
        """Evict the lowest-priority running slot (strictly lower than
        ``incoming`` — equal priorities never preempt, so a preempted
        victim cannot bounce the request that displaced it)."""
        if not self.sched.preempt:
            return False
        victims = []
        for slot, rid in self.pool.occupied():
            req = self.requests[rid]
            if req.state in (PREFILLING, DECODING):
                victims.append((req.priority, -(req.admit_seq or 0),
                                slot, rid))
        if not victims:
            return False
        victims.sort()                 # lowest priority, youngest first
        pr, _, slot, rid = victims[0]
        if pr >= incoming.priority:
            return False
        self._evict(self.requests[rid], slot)
        return True

    def _evict(self, req: Request, slot: int) -> None:
        """Preempt one running request back to the queue, publishing its
        computed KV chunks to the prefix trie first so the later resume
        is mostly trie splices (PREFILLING partial caches are dense —
        always exact; DECODING rows publish only when the pool KV is
        dense, since quantized rows would break splice exactness)."""
        if req.state == PREFILLING:
            job = self._prefills.get(req.rid)
            if job is not None and self.prefix is not None \
                    and job.cache is not None:
                k_full = job.next // self.sched.prefill_chunk
                if self.paged:
                    # shadow-only publish (no blocks allocated yet)
                    self._publish_blocks_paged(job.seq, job.cache, k_full)
                else:
                    self._publish_blocks(job.seq, job.cache, k_full)
            self._cancel_prefill_job(req.rid)
        else:                           # DECODING
            if self.paged:
                # the victim KEEPS its blocks (table row moves to the
                # request, refcounts unchanged): resume is an exact
                # zero-recompute reattach even for quantized KV — the
                # publish path below could not splice those (PR 7 gap)
                row = self._tables_host[slot]
                req.blocks = [int(b) for b in row]
                row[:] = 0
                self._tables_dirty = True
            elif self.prefix is not None and not self.scfg.kv_quant:
                self._publish_pool_row(req, slot)
            self._deactivate_slot(slot)
        self.pool.release(slot)
        req.slot = None
        req.transition(PREEMPTED)
        req.transition(QUEUED)
        req.preemptions += 1
        self.counters["preempted"] += 1
        self.queue.append(req.rid)

    def _publish_pool_row(self, req: Request, slot: int) -> None:
        """Publish a preempted DECODING slot's KV — the prompt AND the
        tokens it produced — as trie chunks keyed by ``prompt+out[:-1]``
        (dense pool rows only; the prefix gate already guarantees
        ring == cache_len, so slot == position and rows are extractable).
        """
        seq = req.resume_tokens()
        c = self.sched.prefill_chunk
        k_full = len(seq) // c
        if k_full <= 0:
            return
        row = jax.tree.map(lambda a: a[:, slot:slot + 1], self._cache)
        self._publish_blocks(seq, row, k_full)

    # ------------------------------------------------------------------
    # paged block pool (DESIGN.md §13)
    # ------------------------------------------------------------------

    def _on_trie_evict(self, payload) -> None:
        """Trie eviction unpins: drop the trie's refcount on the shared
        pool block (shadow-only payloads never took one)."""
        if isinstance(payload, _PagedBlock) and payload.block_id is not None:
            self.block_pool.unref(payload.block_id)

    def _release_slot_blocks(self, slot: int) -> None:
        """Drop this slot's table references (request retired); blocks
        the trie still pins stay live for future prefix hits."""
        if not self.paged:
            return
        row = self._tables_host[slot]
        for bid in row:
            if bid:
                self.block_pool.unref(int(bid))
        row[:] = 0
        self._tables_dirty = True

    def _free_req_blocks(self, req: Request) -> None:
        """Drop a queued PREEMPTED victim's saved table row (deadline
        expiry, or reclaimed under pool pressure — it falls back to the
        recompute-resume path, which stays exact)."""
        if req.blocks:
            for bid in req.blocks:
                if bid:
                    self.block_pool.unref(int(bid))
        req.blocks = None

    def _reclaim_blocks(self, needed: int) -> None:
        """Free pool blocks until ``needed`` are available: first evict
        unpinned trie leaves (pure cache — cheapest to drop), then drop
        queued preemption victims' saved tables (costs them a recompute
        resume, never correctness)."""
        while self.block_pool.n_free < needed and self.prefix is not None:
            if not self.prefix.evict_unpinned(1):
                break
        if self.block_pool.n_free >= needed:
            return
        for rid in list(self.queue):
            if self.block_pool.n_free >= needed:
                break
            req = self.requests[rid]
            if req.blocks:
                self._free_req_blocks(req)

    def _paged_room_for(self, req: Request) -> bool:
        """Admission gate: enough free blocks for this request's table
        (reattaches bring their own) on top of every outstanding
        PREFILLING reservation, reclaiming if short."""
        if req.blocks is not None:
            return True                # reattach brings its own blocks
        needed = self._bps * (1 + len(self._paged_reserved))
        if self.block_pool.n_free >= needed:
            return True
        self._reclaim_blocks(needed)
        return self.block_pool.n_free >= needed

    def _pool_starved(self) -> bool:
        """True when no future step can free a block without outside
        help: nothing running, nothing reserved, no victim tables, and
        the trie already drained of unpinned leaves — admission must
        terminally reject instead of backpressuring forever."""
        if self._paged_reserved or self.pool.occupied():
            return False
        if any(self.requests[rid].blocks for rid in self.queue):
            return False
        return self.block_pool.n_free < self._bps

    def _paged_insert_row(self, slot: int, row_cache, tok, plen, mnt,
                          eos, steps, dense: bool = False) -> None:
        """Allocate a full table for ``slot`` and scatter the batch=1
        row into its blocks (monolithic admission: nothing shared)."""
        bids = np.asarray(self.block_pool.alloc(self._bps), np.int32)
        self._tables_host[slot] = bids
        self._tables_dirty = True
        write = jnp.ones((self._bps,), bool)
        fn = self._insert_dense_paged if dense else self._insert_paged
        self._pool_cache, self._state = fn(
            self._pool_cache, self._state, row_cache, jnp.asarray(bids),
            write, slot, tok, plen, mnt, eos, steps)

    def _reattach_blocks(self, req: Request) -> None:
        """Zero-recompute preemption resume: the victim kept its blocks
        pinned across eviction, so resuming is a table re-attach plus a
        scalar state restore — exact for ANY KV format, including the
        quantized rows the legacy publish path could not splice (the
        PR 7 gap)."""
        seq = req.resume_tokens()
        req.slot = self.pool.acquire(req.rid)
        req.transition(DECODING)
        self._tables_host[req.slot] = np.asarray(req.blocks, np.int32)
        self._tables_dirty = True
        req.blocks = None
        eos = -1 if req.eos_id is None else req.eos_id
        self._state = self._reattach(
            self._state, req.slot, req.out[-1], len(seq) - 1,
            req.max_new_tokens, eos, len(req.out))
        # the whole resume context arrives without recompute
        req.resume_splice_tokens += len(seq)
        req.resume_total_tokens += len(seq)
        self.resume_splice_tokens += len(seq)
        self.prefill_tokens_skipped += len(seq)

    def _spliced_row_cache_paged(self, pinned):
        """Paged prefix splice: device-resident shadow chunks are DUSed
        into a fresh device row — no host assembly, no upload
        (``splice_host_transfers`` stays 0)."""
        row = self._fresh_row()
        c = self.sched.prefill_chunk
        for i, node in enumerate(pinned):
            row = jax.tree.map(
                lambda dst, src, i=i: jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), i * c, axis=2),
                row, node.payload.shadow)
        return row

    def _publish_blocks_paged(self, seq: Sequence[int], cache,
                              k_full: int, bids=None) -> None:
        """Paged trie publish: payloads are device chunk slices (shadow)
        — no host transfer.  When the producer's own table ``bids`` are
        known (final-chunk insert), upgrade shadow-only payloads along
        the path with a pinned block id so later consumers share the
        pool bytes zero-copy."""
        if k_full <= 0 or self.prefix is None:
            return
        c = self.sched.prefill_chunk
        payloads = [_PagedBlock(shadow=jax.tree.map(
            lambda a, i=i: jax.lax.slice_in_dim(a, i * c, (i + 1) * c,
                                                axis=2), cache))
            for i in range(k_full)]
        self.prefix.insert(list(seq), payloads)
        if bids is None:
            return
        for i, node in enumerate(self.prefix.path(list(seq), k_full)):
            pb = node.payload
            if isinstance(pb, _PagedBlock) and pb.block_id is None:
                pb.block_id = int(bids[i])
                self.block_pool.ref(int(bids[i]))

    # ------------------------------------------------------------------
    # admission (per-slot prefill-insert)
    # ------------------------------------------------------------------

    def _admit(self, now: Optional[float] = None) -> List[Request]:
        if self._chunked:
            return self._admit_chunked(now)
        completed = []
        while self.queue:
            rid = self._next_admittable(now)
            if rid is None:
                break                  # offered-load replay: not here yet
            req = self.requests[rid]
            if not self.pool.n_free and not self._preempt_for(req):
                break
            if self.paged and not self._paged_room_for(req):
                if self._pool_starved():
                    self.queue.remove(rid)
                    req.transition(REJECTED, "pool_exhausted")
                    self.counters["rejected"] += 1
                    completed.append(req)
                    continue
                break                  # backpressure: a slot will free
            self.queue.remove(rid)
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            req.t_admit = now
            self.counters["admitted"] += 1
            resumed = bool(req.out)
            if resumed:
                self.counters["resumed"] += 1
            if self.paged and req.blocks is not None:
                self._reattach_blocks(req)     # zero-recompute resume
                continue
            seq = req.resume_tokens()
            self.prefill_tokens_computed += len(seq)

            toks = np.asarray([seq], np.int32)
            lens = None
            if self._mask_pads and self.sched.bucket_prompts:
                w = bucket_cache_len(len(seq), floor=8)
                padded = np.zeros((1, w), np.int32)
                padded[0, w - len(seq):] = seq
                toks = padded
                lens = jnp.asarray([len(seq)], jnp.int32)
            key = jax.random.fold_in(self._key, rid)
            self.n_prefills += 1
            tok, row_cache = self._prefill(self.params, jnp.asarray(toks),
                                           lens, key)
            eos = -1 if req.eos_id is None else req.eos_id
            if resumed:
                # mid-decode resume: the newest emitted token (out[-1],
                # not yet in KV) is the in-flight token; device steps
                # start at len(out) so the budget rule lines up
                req.transition(DECODING)
                req.slot = self.pool.acquire(rid)
                if self.paged:
                    self._paged_insert_row(
                        req.slot, row_cache, req.out[-1], len(seq),
                        req.max_new_tokens, eos, len(req.out))
                else:
                    self._cache, self._state = self._insert(
                        self._cache, self._state, row_cache, req.slot,
                        req.out[-1], len(seq), req.max_new_tokens, eos,
                        len(req.out))
                continue
            with spans.span(spans.SERVE_SYNC):
                first = int(tok[0])
            req.out.append(first)
            self._emitted_tokens += 1
            if req.finished_by(first, 1):
                req.transition(COMPLETED)   # budget of 1 / instant EOS
                self.counters["completed"] += 1
                completed.append(req)
                continue
            req.slot = self.pool.acquire(rid)
            req.transition(DECODING)
            if self.paged:
                self._paged_insert_row(req.slot, row_cache, tok[0],
                                       len(seq), req.max_new_tokens, eos, 1)
            else:
                self._cache, self._state = self._insert(
                    self._cache, self._state, row_cache, req.slot, tok[0],
                    len(seq), req.max_new_tokens, eos, 1)
        return completed

    # ------------------------------------------------------------------
    # chunked admission (one prefill chunk per tick; DESIGN.md §8)
    # ------------------------------------------------------------------

    def _admit_chunked(self, now: Optional[float] = None) -> List[Request]:
        """Reserve a slot per queued request (state PREFILLING) and queue
        its prefill job; no compute happens here — chunks advance one per
        tick in :meth:`_prefill_tick`, so a long prompt can never stall a
        decode tick for more than one chunk's worth of work.  The prefix
        lookup is deliberately NOT done here: it happens when the job
        starts prefilling, so a burst of requests sharing a system
        prompt admitted together still hits the chunks the first sharer
        publishes (admission-time lookup would miss every in-flight
        sharer — the dominant pattern the trie exists for)."""
        rejected = []
        while self.queue:
            rid = self._next_admittable(now)
            if rid is None:
                break
            req = self.requests[rid]
            if not self.pool.n_free and not self._preempt_for(req):
                break
            if self.paged and not self._paged_room_for(req):
                if self._pool_starved():
                    self.queue.remove(rid)
                    req.transition(REJECTED, "pool_exhausted")
                    self.counters["rejected"] += 1
                    rejected.append(req)
                    continue
                break                  # backpressure: a slot will free
            self.queue.remove(rid)
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            req.t_admit = now
            self.counters["admitted"] += 1
            if req.preemptions:
                self.counters["resumed"] += 1
            if self.paged and req.blocks is not None:
                self._reattach_blocks(req)     # zero-recompute resume
                continue
            req.slot = self.pool.acquire(rid)
            req.transition(PREFILLING)
            if self.paged:
                # blocks allocate at the final-chunk insert; hold them
                # back from later admissions until then
                self._paged_reserved.add(rid)
            self._prefills[rid] = _PrefillJob(rid=rid,
                                              seq=req.resume_tokens(),
                                              cache=None, next=0, pinned=[])
            self._prefill_q.append(rid)
        return rejected

    def _start_prefill(self, req: Request, job: _PrefillJob) -> None:
        """First chunk of a job: prefix lookup + partial-cache creation.
        Misses get device-side zeros (no host traffic); hits assemble the
        spliced rows on host and upload once."""
        matched, pinned = (self.prefix.lookup(job.seq)
                           if self.prefix is not None else (0, []))
        req.prefix_hit_tokens = matched
        self.prefill_tokens_skipped += matched
        if req.preemptions:
            # preemption-resume cost accounting: spliced vs recomputed
            req.resume_splice_tokens += matched
            req.resume_total_tokens += len(job.seq)
            self.resume_splice_tokens += matched
            self.resume_recompute_tokens += len(job.seq) - matched
        job.pinned = pinned
        job.next = matched
        if pinned:
            job.cache = (self._spliced_row_cache_paged(pinned) if self.paged
                         else self._spliced_row_cache(pinned))
        else:
            job.cache = self._fresh_row()

    def _spliced_row_cache(self, pinned):
        """Fresh dense batch=1 partial cache with prefix-trie blocks
        copied in at their absolute positions (slot == position: the
        prefix gate requires every ring to cover cache_len)."""
        self.splice_host_transfers += 1        # host assembly + upload
        host = jax.tree.map(np.copy, self._row_template)
        c = self.sched.prefill_chunk
        for i, node in enumerate(pinned):
            jax.tree.map(
                lambda dst, src, i=i: dst.__setitem__(
                    (slice(None), slice(None),
                     slice(i * c, (i + 1) * c)), src),
                host, node.payload)
        return jax.tree.map(jnp.asarray, host)

    def _prefill_tick(self) -> List[Request]:
        """Advance the OLDEST prefilling request by one chunk; on its
        final chunk, sample the first token (resumes reuse their
        in-flight token instead), publish full chunks to the prefix trie,
        and splice the (kv-quantized) row into the pool."""
        if not self._prefill_q:
            return []
        rid = self._prefill_q[0]
        job = self._prefills[rid]
        req = self.requests[rid]
        cw = self.sched.prefill_chunk
        n = len(job.seq)
        with spans.span(spans.SERVE_PREFILL_CHUNK, rid=rid) as sp:
            if job.cache is None:
                self._start_prefill(req, job)
            take = min(cw, n - job.next)
            sp.set_metadata(tokens=take, start=job.next)
            toks = np.zeros((1, cw), np.int32)
            toks[0, :take] = job.seq[job.next:job.next + take]
            key = jax.random.fold_in(self._key, rid)
            self.n_prefills += 1
            req.prefill_chunks += 1
            tok, job.cache = self._chunk(
                self.params, job.cache, jnp.asarray(toks),
                jnp.asarray([job.next], jnp.int32),
                jnp.asarray([take], jnp.int32), key)
            job.next += take
            self.prefill_tokens_computed += take
        if job.next < n:
            return []

        # final chunk: the request leaves PREFILLING
        with spans.span(spans.SERVE_PREFILL_FINISH, rid=rid):
            self._prefill_q.popleft()
            del self._prefills[rid]
            if self.paged:
                return self._finish_prefill_paged(req, job, n, tok)
            return self._finish_prefill_dense(req, job, n, tok)

    def _finish_prefill_dense(self, req: Request, job: _PrefillJob, n: int,
                              tok) -> List[Request]:
        """Dense-pool final chunk: publish full chunks to the prefix
        trie, take the first token (resumes keep their in-flight one) and
        insert the quantized row at the request's slot."""
        cw = self.sched.prefill_chunk
        if self.prefix is not None:
            self._publish_blocks(job.seq, job.cache, n // cw)
            self.prefix.release(job.pinned)
        eos = -1 if req.eos_id is None else req.eos_id
        if req.out:
            # preemption resume: out[-1] is the in-flight token (never
            # written to KV); device steps resume at len(out)
            req.transition(DECODING)
            self._cache, self._state = self._insert_dense(
                self._cache, self._state, job.cache, req.slot, req.out[-1],
                n, req.max_new_tokens, eos, len(req.out))
            return []
        with spans.span(spans.SERVE_SYNC):
            first = int(tok[0])
        req.out.append(first)
        self._emitted_tokens += 1
        if req.finished_by(first, 1):
            req.transition(COMPLETED)   # budget of 1 / instant EOS
            self.counters["completed"] += 1
            self.pool.release(req.slot)
            req.slot = None
            return [req]
        req.transition(DECODING)
        self._cache, self._state = self._insert_dense(
            self._cache, self._state, job.cache, req.slot, tok[0], n,
            req.max_new_tokens, eos, 1)
        return []

    def _finish_prefill_paged(self, req: Request,
                              job: _PrefillJob, n: int, tok
                              ) -> List[Request]:
        """Paged final chunk: build the slot's block table (matched trie
        chunks with an attached block id are shared by table append —
        zero copies; the rest allocate from the pool), scatter the
        quantized row into the owned blocks, publish shadows + upgrade
        the path with this producer's block ids."""
        cw = self.sched.prefill_chunk
        k_full = n // cw
        eos = -1 if req.eos_id is None else req.eos_id
        if not req.out:
            with spans.span(spans.SERVE_SYNC):
                first = int(tok[0])
            req.out.append(first)
            self._emitted_tokens += 1
            if req.finished_by(first, 1):
                # budget of 1 / instant EOS: no decode slot, no blocks —
                # publish shadow-only chunks so later sharers still hit
                if self.prefix is not None:
                    self._publish_blocks_paged(job.seq, job.cache, k_full)
                    self.prefix.release(job.pinned)
                self._paged_reserved.discard(req.rid)
                req.transition(COMPLETED)
                self.counters["completed"] += 1
                self.pool.release(req.slot)
                req.slot = None
                return [req]
        bids = np.zeros((self._bps,), np.int32)
        write = np.ones((self._bps,), bool)
        shared = 0
        for i, node in enumerate(job.pinned):
            pb = node.payload
            if isinstance(pb, _PagedBlock) and pb.block_id is not None:
                # chunk i's bytes are a pure function of seq[:(i+1)*cw]
                # (deterministic chunked prefill), so the producer's
                # quantized block IS what this insert would write
                bids[i] = pb.block_id
                self.block_pool.ref(pb.block_id)
                write[i] = False
                shared += 1
        own_idx = [i for i in range(self._bps) if write[i]]
        own = self.block_pool.alloc(len(own_idx))
        for i, b in zip(own_idx, own):
            bids[i] = b
        self._paged_reserved.discard(req.rid)
        self.prefix_blocks_shared += shared
        if self.prefix is not None:
            self._publish_blocks_paged(job.seq, job.cache, k_full, bids)
            self.prefix.release(job.pinned)
        req.transition(DECODING)
        self._tables_host[req.slot] = bids
        self._tables_dirty = True
        self._pool_cache, self._state = self._insert_dense_paged(
            self._pool_cache, self._state, job.cache, jnp.asarray(bids),
            jnp.asarray(write), req.slot, req.out[-1], n,
            req.max_new_tokens, eos, len(req.out))
        return []

    def _publish_blocks(self, seq: Sequence[int], cache,
                        k_full: int) -> None:
        """Insert ``seq``'s first ``k_full`` whole chunks into the trie
        from a dense batch=1 cache (a partial prefill cache or an
        extracted pool row).  Block i is a pure function of
        ``seq[:(i+1)*c]`` (deterministic chunked prefill with absolute
        chunk boundaries), so re-computed and cached blocks are
        interchangeable — the trie keeps whichever arrived first."""
        if k_full <= 0 or self.prefix is None:
            return
        self.splice_host_transfers += 1        # device -> host download
        c = self.sched.prefill_chunk
        # slice on device, transfer only the full chunks — not the whole
        # cache_len row (prefix gate: slot == position)
        host = jax.tree.map(
            lambda a: np.asarray(a[:, :, :k_full * c]), cache)
        blocks = [jax.tree.map(
            lambda a, i=i: a[:, :, i * c:(i + 1) * c].copy(), host)
            for i in range(k_full)]
        self.prefix.insert(list(seq), blocks)

    # ------------------------------------------------------------------
    # decode tick (k steps on device, one dispatch)
    # ------------------------------------------------------------------

    def _do_tick(self) -> List[Request]:
        occupied = [(slot, rid) for slot, rid in self.pool.occupied()
                    if self.requests[rid].state == DECODING]
        if not occupied:               # only PREFILLING slots: no decode
            self._inject_bad_slots.clear()
            return []
        self.n_ticks += 1
        with spans.span(spans.SERVE_DECODE_TICK, slots=len(occupied)):
            key = jax.random.fold_in(self._tick_key, self.n_ticks)
            if self.paged:
                if self._tables_dirty:
                    self._tables = jnp.asarray(self._tables_host)
                    self._tables_dirty = False
                self._pool_cache, self._state, em, bad = self._tick(
                    self.params, self._pool_cache, self._tables, self._state,
                    key)
            else:
                self._cache, self._state, em, bad = self._tick(
                    self.params, self._cache, self._state, key)
        with spans.span(spans.SERVE_SYNC):
            em, bad = jax.device_get((em, bad))  # ONE sync per tick: (k, n)
        with spans.span(spans.SERVE_RETIRE):
            return self._retire(occupied, np.asarray(em), np.asarray(bad))

    def _retire(self, occupied, em: np.ndarray, bad: np.ndarray
                ) -> List[Request]:
        """Host side of a tick: append each slot's emitted tokens (the
        (k, n_slots) block), apply the device's termination rule, free
        finished slots and quarantine poisoned ones."""
        injected = self._inject_bad_slots
        self._inject_bad_slots = set()
        terminal = []
        for slot, rid in occupied:
            req = self.requests[rid]
            req.ticks += 1
            if bad[:, slot].any() or slot in injected:
                with spans.span(spans.SERVE_QUARANTINE, rid=rid):
                    terminal += self._quarantine(req, slot)
                continue
            for s in range(self.sched.steps_per_tick):
                t = int(em[s, slot])
                if t < 0:              # done-masked earlier in this tick
                    break
                req.out.append(t)
                self._emitted_tokens += 1
                if req.finished_by(t, len(req.out)):
                    break              # device flagged done at this step
            if req.finished_by(req.out[-1], len(req.out)):
                req.transition(COMPLETED)
                self.counters["completed"] += 1
                self.pool.release(slot)
                self._release_slot_blocks(slot)
                req.slot = None
                terminal.append(req)
        return terminal

    # ------------------------------------------------------------------
    # non-finite quarantine -> jnp-fallback retry (DESIGN.md §10)
    # ------------------------------------------------------------------

    def _quarantine(self, req: Request, slot: int) -> List[Request]:
        """A slot's decode logits went non-finite: free it (everything it
        emitted is suspect — the fallback regenerates from scratch) and
        retry the request ONCE on the jnp reference engine.  FAILED only
        if the fallback faults too (or this is the second quarantine)."""
        self._deactivate_slot(slot)
        self.pool.release(slot)
        self._release_slot_blocks(slot)
        req.slot = None
        self.counters["nan_events"] += 1
        if req.nan_retries >= 1:
            req.out = []
            req.transition(FAILED, "nonfinite_twice")
            self.counters["failed"] += 1
            return [req]
        req.nan_retries += 1
        self.counters["nan_retries"] += 1
        try:
            if req.rid in self._fail_fallback_rids:
                self._fail_fallback_rids.discard(req.rid)
                raise FloatingPointError("injected fallback fault")
            out = self._fallback_engine().generate(
                [req.prompt], [req.max_new_tokens], [req.eos_id])[0]
        except Exception:
            req.out = []
            req.transition(FAILED, "nonfinite_fallback")
            self.counters["failed"] += 1
            return [req]
        req.out = out
        self._emitted_tokens += len(out)
        req.transition(COMPLETED, "nan_fallback")
        self.counters["completed"] += 1
        return [req]
