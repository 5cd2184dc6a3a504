"""The program's own marks on a profiler trace: device time by named scope
and host time by program span (``repro.spans``).

The name stack of each TPU op (the ``jax.named_scope`` path its HLO
metadata holds) is the ``tf_op`` stat of the op's event metadata in the
``.xplane.pb``; ``jax.profiler.ProfileData`` gives the events but not
their metadata's stats, so :func:`op_stacks` reads those from the file
itself.  The program's host spans are ``TraceAnnotation`` events on the
host plane, on the same clock, with their keyword arguments as stats.
From these:

- scope time: each op's self time (nested ops, such as a loop's body,
  are taken out of their parent, as ``trace.reduce_events`` does)
  counted under the path of program scopes in its name stack; ops under
  ``transpose(`` are the backward pass;
- ``kv_cache_share`` / ``update_share``: self time of ops with
  ``kv_cache`` / ``update`` anywhere in their path, over busy time, %;
- ``host_ms_per_step``: per step span (``serve.step``, ``train.step``),
  the mean over the steps that ended in the window of the step's length
  less its device syncs (``serve.sync`` / ``train.sync``), ms;
- ``idle_by_span``: each idle gap of the first device cut at span edges
  and put down to the innermost program span open over each piece.

    PYTHONPATH=src python3 -m bench.harness.program_trace <.xplane.pb or dir>

prints the summary of a trace captured with ``--profile DIR`` or a
benchmark run.  Device numbers are averaged over the devices.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench.harness.trace import clip, merge
from repro import spans as S

STACK_STAT = "tf_op"
STEP_SYNC = {S.SERVE_STEP: S.SERVE_SYNC, S.TRAIN_STEP: S.TRAIN_SYNC}
# JAX's transformations wrap the name stack segment they apply to; a
# ``jit(name)`` segment is a function's name, never a scope
_TRANSFORMED = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")

Span = Tuple[str, float, float, Dict]


def scope_path(stack: str) -> Tuple[Tuple[str, ...], bool]:
    """The program scopes along an op's name stack, outermost first, and
    whether the op is in the backward pass.  A segment such as
    ``transpose(jvp(attn))`` names the scope ``attn``; of an op that XLA
    merged from several (``a;b``) the first name counts."""
    stack = stack.split(";")[0]
    path: List[str] = []
    for seg in stack.split("/"):
        m = _TRANSFORMED.match(seg)
        while m:
            seg = m.group(1)
            m = _TRANSFORMED.match(seg)
        if seg in S.SCOPES and (not path or path[-1] != seg):
            path.append(seg)
    return tuple(path), "transpose(" in stack


def label(path: Tuple[str, ...], backward: bool) -> str:
    name = "/".join(path) if path else "(no scope)"
    return name + " (backward)" if backward and path else name


def _self_times(ops, lo: float, hi: float):
    """(stack, self time) of each op event inside [lo, hi]."""
    inside = sorted(((max(s, lo), min(s + d, hi) - max(s, lo), st)
                     for st, s, d in ops if lo < s + d and s < hi),
                    key=lambda x: (x[0], -x[1]))
    out: List[Tuple[str, float]] = []
    stack: List[list] = []               # [end, stack, child time, dur]

    def pop():
        _, st, child, dur = stack.pop()
        out.append((st, dur - child))
        if stack:
            stack[-1][2] += dur

    for s, d, st in inside:
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([s + d, st, 0.0, d])
    while stack:
        pop()
    return out


def _innermost(lo: float, hi: float, host: List[Span]):
    """(span name, time) of the pieces of (lo, hi) cut at the edges of the
    spans over it; each piece goes to the innermost span open over all of
    it (nested spans: the latest-starting one)."""
    over = [sp for sp in host if sp[1] < hi and sp[2] > lo]
    edges = sorted({lo, hi} | {x for _, s, e, _ in over for x in (s, e)
                               if lo < x < hi})
    for a, b in zip(edges, edges[1:]):
        open_ = [sp for sp in over if sp[1] <= a and sp[2] >= b]
        yield (max(open_, key=lambda sp: sp[1])[0] if open_
               else "no program span"), b - a


def reduce_program(devices: Dict[str, Dict[str, list]], host: List[Span],
                   window: Tuple[float, float]) -> Dict:
    """The summary from plain event lists: ``devices`` per device
    ``{"modules": [(name, start, dur)], "ops": [(name stack, start,
    dur)]}``, ``host`` the program spans ``(name, start, end, stats)``,
    ``window`` (start, end); all in ns."""
    lo, hi = window
    n = max(len(devices), 1)
    busy = 0.0
    by_label: Dict[str, float] = collections.defaultdict(float)
    under: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for i, (_, ev) in enumerate(sorted(devices.items())):
        mods = merge(clip([(s, s + d) for _, s, d in ev["modules"]], lo, hi))
        busy += sum(b - a for a, b in mods)
        if i == 0:
            edges = [lo] + [x for ab in mods for x in ab] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
        for stack, t in _self_times(ev["ops"], lo, hi):
            path, backward = scope_path(stack)
            by_label[label(path, backward)] += t
            for scope in set(path):
                under[scope] += t
    idle: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        for name, t in _innermost(a, b, host):
            idle[name] += t
    steps: Dict[str, float] = {}
    for step, sync in STEP_SYNC.items():
        own = [(s, e) for name, s, e, _ in host
               if name == step and lo <= e <= hi]
        if own:
            steps[step] = sum(
                (e - s) - sum(se - ss for name, ss, se, _ in host
                              if name == sync and s <= ss and se <= e)
                for s, e in own) / len(own) / 1e6
    share = (lambda t: 100.0 * t / busy) if busy else (lambda t: None)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "kv_cache_share": share(under.get(S.KV_CACHE, 0.0)),
        "update_share": share(under.get(S.UPDATE, 0.0)),
        "unscoped_share": share(by_label.get(label((), False), 0.0)),
        "host_ms_per_step": steps,
        "scope_s": {k: v / n / 1e9 for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])},
        "idle_by_span": {k: v / 1e9 for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
    }


def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of each field of one protobuf message in
    ``buf[pos:end]``: an int for a varint, a (start, end) slice for a
    length-delimited field; fixed-width fields are skipped."""
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        else:
            pos += 8 if kind == 1 else 4
            continue
        yield key >> 3, val


def _varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def op_stacks(path: str) -> Dict[str, Dict[str, str]]:
    """Per TPU device plane, each op's event name (its HLO text) and the
    ``tf_op`` stat of its metadata: the op's name stack.  Read from the
    ``XSpace`` proto (planes 1; a plane's name 2, event metadata 4, stat
    metadata 5; an event metadata's name 2 and stats 5; a stat's metadata
    id 1, string 5, reference 7).  A name whose ops disagree maps to ""."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for num, (p0, p1) in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for fn, val in _fields(buf, p0, p1):
            if fn == 2:
                name = buf[val[0]:val[1]].decode()
            elif fn in (4, 5):                # map<int64, metadata> entries
                entry = dict(_fields(buf, *val))
                if 2 not in entry:
                    continue
                md = list(_fields(buf, *entry[2]))
                if fn == 5:
                    stat_names[entry.get(1, 0)] = next(
                        (buf[v[0]:v[1]].decode() for k, v in md if k == 2), "")
                else:
                    events.append(md)
        if not name.startswith("/device:TPU:"):
            continue
        stacks: Dict[str, str] = {}
        for md in events:
            ev_name, stack = "", ""
            for k, v in md:
                if k == 2:
                    ev_name = buf[v[0]:v[1]].decode(errors="replace")
                elif k == 5:
                    st = dict(_fields(buf, *v))
                    if stat_names.get(st.get(1)) == STACK_STAT:
                        stack = (buf[st[5][0]:st[5][1]].decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            stacks[ev_name] = (stack if stacks.get(ev_name, stack) == stack
                               else "")
        out[name] = stacks
    return out


def read_events(path: str):
    """Device events with their name stacks, the program's host spans,
    and the window: the ``bench.window`` span where the benchmark traced,
    else the extent of the device events."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    stacks = op_stacks(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Span] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    ev["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
                elif line.name == "XLA Ops":
                    named = stacks.get(plane.name, {})
                    ev["ops"] = [(named.get(e.name, ""), e.start_ns,
                                  e.duration_ns) for e in line.events]
            devices[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(("serve.", "train.")):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats)))
    if window is None:
        starts = [s for ev in devices.values() for _, s, _ in ev["modules"]]
        ends = [s + d for ev in devices.values()
                for _, s, d in ev["modules"]]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return devices, host, window


def read(path: str) -> Dict:
    """The summary of one ``.xplane.pb`` (:func:`reduce_program`)."""
    return reduce_program(*read_events(path))


def find(path: str) -> Optional[str]:
    """The newest ``.xplane.pb`` at or under ``path``."""
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


if __name__ == "__main__":
    f = find(sys.argv[1])
    if f is None:
        sys.exit(f"no .xplane.pb at or under {sys.argv[1]}")
    print(json.dumps(read(f), indent=1))
