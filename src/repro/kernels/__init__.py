# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

"""Shared helpers for the Pallas kernel packages."""

from __future__ import annotations

import re
from collections import Counter
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for one ``pallas_call``.

    An explicit flag wins.  Otherwise kernels compile with Mosaic on a TPU
    backend and run in the Pallas interpreter on every other backend (the
    CPU test suite exercises the same kernel dataflow that way).  This is
    the one place that decision is made.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# the name scope a pallas_call opens, e.g. ".../opt_step/pallas_call" or,
# under vmap, ".../vmap(opt_step)/pallas_call"
_KERNEL_OP = re.compile(r'op_name="[^"]*/([^/"]+)/pallas_call"')


def compiled_kernels(hlo_text: str) -> Counter:
    """Count the Mosaic kernels in a compiled program's HLO text, by the
    ``name`` each ``pallas_call`` is given: one count per
    ``tpu_custom_call`` instruction.  A kernel that fell back to the jnp
    path or ran in interpret mode leaves no such instruction."""
    out: Counter = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_OP.search(line)
            out[re.findall(r"\w+", m.group(1))[-1] if m else "?"] += 1
    return out
