"""The collectives of a window traced on several chips.

A TensorCore runs its ops one at a time, and an op's self time is its
time less that of the ops nested in it (``trace.reduce_events``).  So the
self time of a collective is time in which the core did nothing but move
data between chips or wait for it: a synchronous collective's whole
length, and of an asynchronous pair (``-start`` ... ``-done``) the
``-done``'s wait for what the compute in between did not hide.
``collective_s`` is that time per device, averaged over the devices.

An op is a collective by its HLO opcode, read from the event's
instruction text (``opcode``), whatever the compiler named it.  A fusion
or a generic ``async-start``/``async-done`` wrapper holds its collective
in a called computation the event does not show: it counts where the
compiler named it after that collective (``reduce-scatter-start.3 = ...
async-start(...)``), and an asynchronous slice (``slice-start``) does not.

``MeshCapture`` is ``trace.Capture`` whose summary also holds
``collective_s``, read from the same trace before it is deleted.
"""

from __future__ import annotations

import glob
import re
import shutil
from typing import Dict, Optional, Tuple

from bench.harness import trace
from bench.harness.program_trace import _self_times

COLLECTIVES = frozenset(
    op + form
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
    for form in ("", "-start", "-done"))


# the opcode: the first word followed by "(" after the result type; in the
# type a "(" follows ":" (tiling), ")" (memory space) or ", " (a tuple)
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def opcode(op_text: str) -> str:
    """The HLO opcode of an op event's text, ``%name = type opcode(...)``."""
    m = _OPCODE.search(op_text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


_WRAPPERS = ("fusion", "async-start", "async-update", "async-done")


def is_collective(op_text: str) -> bool:
    op = opcode(op_text)
    if op in COLLECTIVES:
        return True
    return op in _WRAPPERS and re.split(r"[._]", trace.op_label(op_text),
                                        maxsplit=1)[0] in COLLECTIVES


def collective_s(device_planes: Dict[str, Dict[str, list]],
                 window: Tuple[float, float]) -> float:
    """Self time of the collective ops (``is_collective``) inside
    ``window`` (ns), in seconds, averaged over the devices;
    ``device_planes`` as ``trace.reduce_events`` takes them."""
    lo, hi = window
    total = sum(t for ev in device_planes.values()
                for text, t in _self_times(ev["ops"], lo, hi)
                if is_collective(text))
    return total / max(len(device_planes), 1) / 1e9


class MeshCapture(trace.Capture):
    """``trace.Capture`` whose summary adds ``collective_s``."""

    def finish(self) -> Optional[Dict]:
        if self.summary is None and self._dir is not None:
            files = glob.glob(f"{self._dir}/**/*.xplane.pb", recursive=True)
            devices, spans, window = trace.read_xplane(files[0],
                                                       self.spans.names())
            self.summary = trace.reduce_events(devices, spans, window)
            self.summary["collective_s"] = collective_s(devices, window)
            shutil.rmtree(self._dir, ignore_errors=True)
        return self.summary
