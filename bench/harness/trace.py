"""Host spans, the profiler capture of a window, and the reduction from
the trace to the numbers per-layer metrics read.

The trace's device plane (``/device:TPU:<n>``) has an ``XLA Modules``
line (one event per program execution) and an ``XLA Ops`` line (one event
per HLO op, nested inside loops).  A Pallas kernel is an ``XLA Ops`` event
whose text carries ``custom_call_target="tpu_custom_call"``; its name is
the op name with the ``vmap_`` prefixes and numeric suffix taken off
(``%vmap_opt_step_.13`` -> ``opt_step``).  Host spans are
``TraceAnnotation`` events on host lines, on the same clock.
"""

from __future__ import annotations

import collections
import glob
import re
import shutil
import time
from typing import Dict, List, Optional, Tuple

TRACE_S = 5.0            # seconds of the window a ``--trace 1`` run traces
TOP = 10
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def kernel_name(op_text: str) -> str:
    name = op_text.split(" = ")[0].strip().lstrip("%")
    name = re.sub(r"\.\d+.*$", "", name)
    name = re.sub(r"^(vmap_)+", "", name)
    return name.strip("_")


def op_label(op_text: str) -> str:
    """A short stable label of an HLO op event: kernels by kernel name,
    other ops by their opcode word (``fusion``, ``while``, ``copy`` ...)."""
    if _KERNEL.search(op_text):
        return kernel_name(op_text)
    name = op_text.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"\.\d+.*$", "", name)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce_events(device_planes: Dict[str, Dict[str, list]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Tuple[float, float]) -> Dict:
    """The numbers the metrics read, from plain event lists.

    ``device_planes``: per device, ``{"modules": [(name, start, dur)],
    "ops": [(text, start, dur)]}`` in ns; ``host_spans``: (name, start,
    end) of the benchmark's host spans; ``window``: (start, end) in ns.
    Busy time is the union of program executions on a device; a kernel's
    time is the sum of its events' device durations; an op's share of the
    breakdown is its self time (nested ops, such as a loop's body, are
    taken out of their parent); an idle gap goes to the host span that
    overlaps it most.  Device numbers are averaged over the devices."""
    lo, hi = window
    n = max(len(device_planes), 1)
    busy = 0.0
    kernels: Dict[str, float] = collections.defaultdict(float)
    ops: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for i, (dev, ev) in enumerate(sorted(device_planes.items())):
        mods = merge(clip([(s, s + d) for _, s, d in ev["modules"]], lo, hi))
        busy += sum(b - a for a, b in mods)
        if i == 0:
            edges = [lo] + [x for ab in mods for x in ab] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
        inside = sorted(((max(s, lo), min(s + d, hi) - max(s, lo), t)
                         for t, s, d in ev["ops"] if lo < s + d and s < hi),
                        key=lambda x: (x[0], -x[1]))
        stack: List[list] = []          # [end, label, child time, dur]

        def pop():
            _, lab, child, dur = stack.pop()
            ops[lab] += dur - child
            if stack:
                stack[-1][2] += dur

        for s, d, text in inside:
            while stack and stack[-1][0] <= s:
                pop()
            lab = op_label(text)
            if _KERNEL.search(text):
                kernels[lab] += d
            stack.append([s + d, lab, 0.0, d])
        while stack:
            pop()
    spans = sorted(host_spans, key=lambda s: s[1])

    def doing(a, b):
        """The host span that overlaps the gap (a, b) most."""
        best, cover = "no host span", 0.0
        for name, s, e in spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    by_host: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        by_host[doing(a, b)] += (b - a)
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                by_host.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def read_xplane(path: str, span_names: List[str]):
    """Event lists of a profiler ``.xplane.pb``: device planes, the
    benchmark's host spans, and the ``bench.window`` span's extent."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    ev[key] = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
            devices[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in span_names:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return devices, spans, window


class Spans:
    """The benchmark's host spans around its calls into the program:
    profiler ``TraceAnnotation``s (so they land on the trace's clock) and a
    host-clock record for counting what the traced window held."""

    def __init__(self):
        self.done: List[Tuple[str, float, float]] = []
        self._open: Dict[str, Tuple[object, float]] = {}

    def open(self, name: str) -> None:
        import jax
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open[name] = (ann, time.perf_counter())

    def close(self, name: str) -> None:
        if name in self._open:
            ann, t0 = self._open.pop(name)
            ann.__exit__(None, None, None)
            self.done.append((name, t0, time.perf_counter()))

    class _Ctx:
        def __init__(self, spans, name):
            self.spans, self.name = spans, name

        def __enter__(self):
            self.spans.open(self.name)

        def __exit__(self, *exc):
            self.spans.close(self.name)

    def span(self, name: str):
        return Spans._Ctx(self, name)

    def names(self) -> List[str]:
        return sorted({n for n, _, _ in self.done} | set(self._open))


class Capture:
    """A profiler trace of part of the window, in a run of its own
    (``--trace 1``).  ``start`` at the window's opening, ``stop`` after
    ``TRACE_S``; ``summary`` is ``reduce_events`` of what was captured."""

    def __init__(self, enabled: bool, spans: Spans):
        self.enabled, self.spans = enabled, spans
        self.active = False
        self.summary: Optional[Dict] = None
        self.t0 = self.t1 = None
        self._dir = None
        self._win = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        from bench.harness.common import tmpdir
        self._dir = tmpdir("trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._win = jax.profiler.TraceAnnotation("bench.window")
        self._win.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def stop(self, now: Optional[float] = None) -> None:
        """End the capture; the trace is read later, by ``finish``, so
        that reading it costs the window nothing."""
        if not self.active:
            return
        import jax
        self.t1 = now if now is not None else time.perf_counter()
        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def finish(self) -> Optional[Dict]:
        """Read and reduce the captured trace (once), then delete it."""
        if self.summary is None and self._dir is not None:
            files = glob.glob(f"{self._dir}/**/*.xplane.pb", recursive=True)
            devices, spans, window = read_xplane(files[0],
                                                 self.spans.names())
            self.summary = reduce_events(devices, spans, window)
            shutil.rmtree(self._dir, ignore_errors=True)
        return self.summary

    def count(self, spans: Spans, name: str) -> int:
        """Spans ``name`` that ended inside the traced window."""
        return sum(1 for n, _, e in spans.done
                   if n == name and self.t0 <= e <= self.t1)
