"""The operations and bytes a step or a kernel call NEEDS, from the
model's published shapes and the counts of work done in the window.

Never from a kernel's block shapes or from the compiled program: a later
change that re-implements a kernel is read against the same work.  FLOPs
count a multiply-add as 2; recomputation (remat) is not counted.
"""

from __future__ import annotations

from typing import Dict, Iterable

from bench.harness.weights import dims

ACT_BYTES = 2          # activations are bfloat16 (the configuration's dtype)
INT4_BYTES = 0.5
SCALE_BYTES = 4        # one float32 scale


def matrices(c: Dict) -> Dict[str, tuple]:
    """(K, N) of each weight matrix of one layer (``x @ w``: K in, N out)."""
    m = dims(c)
    d, qd, kvd, ff = m["d"], m["h"] * m["hd"], m["g"] * m["hd"], m["ff"]
    return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
            "w_up": (d, ff), "w_gate": (d, ff), "w_down": (ff, d)}


def layer_matmul_params(c: Dict) -> int:
    return sum(k * n for k, n in matrices(c).values())


def matmul_params(c: Dict) -> int:
    """Parameters that multiply activations: every layer matrix plus the
    (tied) head.  The embedding lookup itself is not a matmul."""
    m = dims(c)
    return m["L"] * layer_matmul_params(c) + m["V"] * m["d"]


def all_params(c: Dict) -> int:
    """Every trained parameter: layer matrices, two norm scales per layer,
    the embedding (tied head) and the final norm."""
    m = dims(c)
    return (m["L"] * (layer_matmul_params(c) + 2 * m["d"])
            + m["V"] * m["d"] + m["d"])


def attn_flops_causal(c: Dict, seq: int) -> float:
    """Forward score + value FLOPs of one causal row of ``seq`` tokens
    over all layers: each query attends (its position + 1) keys."""
    m = dims(c)
    keys = seq * (seq + 1) / 2
    return 4.0 * m["L"] * m["h"] * m["hd"] * keys


def train_step_flops(c: Dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of one training step."""
    fwd = (2.0 * matmul_params(c) * batch * seq
           + batch * attn_flops_causal(c, seq))
    return 3.0 * fwd


def opt_step_bytes(c: Dict) -> float:
    """The fused LOTION-AdamW update per step: float32 weights, gradients
    and both moments read, weights and both moments written: 28 B/param."""
    return 28.0 * all_params(c)


def token_flops(c: Dict, ctx: float) -> float:
    """Forward FLOPs of one token that attends ``ctx`` keys."""
    m = dims(c)
    return (2.0 * matmul_params(c)
            + 4.0 * m["L"] * m["h"] * m["hd"] * ctx)


def prefill_flops(c: Dict, start: int, n: int) -> float:
    """Forward FLOPs of prompt tokens ``start .. start+n-1``."""
    keys = n * start + n * (n + 1) / 2
    m = dims(c)
    return (2.0 * matmul_params(c) * n
            + 4.0 * m["L"] * m["h"] * m["hd"] * keys)


def wqt_matmul_bound_s(c: Dict, rows: int, calls: float,
                       peak_flops: float, peak_bw: float) -> float:
    """Least time of ``calls`` passes of ``rows`` activation rows through
    every int4 layer matrix: per matrix, the larger of its FLOPs over peak
    and its bytes (int4 codes, its scale, bf16 rows in and out) over
    bandwidth."""
    m = dims(c)
    t = 0.0
    for k, n in matrices(c).values():
        flops = 2.0 * rows * k * n
        byts = k * n * INT4_BYTES + SCALE_BYTES + rows * (k + n) * ACT_BYTES
        t += max(flops / peak_flops, byts / peak_bw)
    return t * m["L"] * calls


def decode_attn_bound_s(c: Dict, contexts: Iterable[int],
                        peak_flops: float, peak_bw: float) -> float:
    """Least time of paged int4 decode attention over the given live
    contexts (one entry per slot per decode step): every layer reads each
    context's int4 K and V codes and their per-vector scales once."""
    m = dims(c)
    total = 0.0
    for ctx in contexts:
        byts = (2 * m["L"] * ctx * m["g"] * (m["hd"] * INT4_BYTES
                                            + SCALE_BYTES)
                + 2 * m["L"] * m["h"] * m["hd"] * ACT_BYTES)
        flops = 4.0 * m["L"] * m["h"] * m["hd"] * ctx
        total += max(flops / peak_flops, byts / peak_bw)
    return total
