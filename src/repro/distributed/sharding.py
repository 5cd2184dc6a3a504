"""Sharding rules: parameter / optimizer-state / activation / cache
PartitionSpecs for the production meshes.

Strategy (see DESIGN.md §4):

* ``model`` axis — Megatron-style tensor parallelism: output-feature dim of
  up/qkv projections, input-feature dim of down/out projections, expert dim
  of MoE weights (EP), vocab dim of embeddings.
* ``data`` axis — data parallelism, plus FSDP-style parameter sharding of
  the *other* large dim of each weight (ZeRO-3 posture: params, grads and
  optimizer state all carry the same 2-D sharding; XLA inserts the
  all-gathers around use sites).
* ``pod`` axis — outer data parallelism (gradient reduction crosses DCN).

Rules are name+shape driven over the flattened param pytree.  Everything
under ``stage/`` is stacked with a leading repeats axis (never sharded).
"""

from __future__ import annotations


import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.qtensor import MATMUL_LEAVES as _QT_WEIGHT_NAMES
from repro.core.qtensor import _NATURAL_LEAVES as _QT_NATURAL

# weights whose LAST dim is the model-parallel (output) dim
_COL_PARALLEL = (
    "wq", "wk", "wv", "w_up", "w_gate", "in_proj", "w_r", "w_k", "w_v",
    "w_g", "w_k_cm", "vision_proj",
)
# weights whose FIRST dim is the model-parallel (input) dim
_ROW_PARALLEL = ("wo", "w_down", "out_proj", "w_o", "w_v_cm")
_REPLICATED_HINTS = (
    "norm", "scale", "bias", "a_log", "d_skip", "decay", "bonus", "mu_",
    "gate_", "xattn_gate", "conv_b", "lora", "router", "mu_base",
)

# QTensor (quantized-storage serving) leaves: the codes/scales children of
# these weight names derive their spec from the WEIGHT's rule so the int
# codes and their scales shard congruently.  Storage is out-major
# (transposed) for matmul operands; the natural gather tables keep their
# dense orientation (DESIGN.md §6).
# (the QTensor name sets _QT_WEIGHT_NAMES/_QT_NATURAL are imported from
# core/qtensor.py above — the convertible-leaf set and the sharding rule
# set must never drift apart)


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        parts.append(str(p.key) if hasattr(p, "key") else str(getattr(p, "idx", p)))
    return "/".join(parts)


def param_spec(path, x, *, fsdp: bool = True, stacked_prefixes=("stage",)) -> P:
    """PartitionSpec for one parameter leaf."""
    name = _leaf_name(path)
    lead: tuple = ()
    ndim = x.ndim
    if any(name.startswith(pfx) for pfx in stacked_prefixes):
        lead = (None,)
        ndim -= 1
    last = name.rsplit("/", 1)[-1]
    dp = "data" if fsdp else None

    # QTensor children: spec comes from the parent weight's rule (codes
    # and scales congruent); storage is transposed except gather tables.
    # The divisibility fixup at placement time prunes axes the (smaller)
    # scales tensors cannot honor, replicating per-tensor (1, 1) scales.
    qt_child = False
    parent = name.split("/")[-2] if "/" in name else ""
    if last in ("codes", "scales") and parent in _QT_WEIGHT_NAMES:
        qt_child, last = True, parent

    if any(h in last for h in _REPLICATED_HINTS) or ndim <= 1:
        return P(*lead)

    if last == "embed":
        if ndim == 3:      # (codebooks, vocab, d)
            spec = P(*lead, None, "model", dp)
        else:
            spec = P(*lead, "model", dp)      # (vocab, d)
    elif last == "lm_head":
        if ndim == 3:      # (codebooks, d, vocab)
            spec = P(*lead, None, dp, "model")
        else:
            spec = P(*lead, dp, "model")      # (d, vocab)
    elif last == "conv_w":
        spec = P(*lead, None, "model")        # depthwise channels
    elif last in ("w_up", "w_gate", "w_down") and ndim == 3:
        # MoE expert weights (e, d, f) / (e, f, d): EP over model
        if last == "w_down":
            spec = P(*lead, "model", None, dp)
        else:
            spec = P(*lead, "model", dp, None)
    elif any(last == c for c in _COL_PARALLEL) and ndim == 2:
        spec = P(*lead, dp, "model")
    elif any(last == r for r in _ROW_PARALLEL) and ndim == 2:
        spec = P(*lead, "model", dp)
    elif ndim == 2:
        spec = P(*lead, dp, "model")          # default: 2-D shard
    else:
        spec = P(*lead)

    if qt_child and last not in _QT_NATURAL and len(spec) >= 2:
        entries = list(spec)
        entries[-1], entries[-2] = entries[-2], entries[-1]
        spec = P(*entries)
    return spec


def widen_dp(mesh, spec: P) -> P:
    """On multi-pod meshes, FSDP/ZeRO shards span the pod axis too
    (multi-node ZeRO-3): every 'data' entry becomes ('pod', 'data').
    Param gathers then cross DCN — the memory/bandwidth trade is recorded
    in EXPERIMENTS §Perf (cell B)."""
    if "pod" not in mesh.axis_names:
        return spec
    out = []
    for entry in spec:
        if entry == "data":
            out.append(("pod", "data"))
        elif isinstance(entry, tuple) and "data" in entry and "pod" not in entry:
            out.append(("pod",) + tuple(entry))
        else:
            out.append(entry)
    return P(*out)


def fix_divisibility(mesh, spec: P, shape) -> P:
    """jit in_shardings require every sharded dim to divide evenly; drop
    mesh axes from dims that don't (e.g. granite's vocab = 49155 = 3*5*29*113
    is indivisible by any power-of-two axis -> replicate that dim)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        size = 1
        for a in axes:
            if shape[i] % (size * mesh.shape[a]) == 0:
                keep.append(a)
                size *= mesh.shape[a]
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


# optimizer-chain state keys holding param-shaped trees (AdamW moments,
# EF-compress carried error): everything after the marker is a param path
_OPT_TREE_KEYS = ("mu", "nu", "err")


def state_shardings(mesh, state_shapes, *, fsdp: bool = True):
    """NamedSharding pytree for the full train state ({params, opt, step}).

    Optimizer moments mirror their parameter's sharding (ZeRO posture).
    ``opt`` may be a flat optimizer dict (legacy), an update-transform
    chain state — a tuple of link states like
    ``({"gnorm"}, {"err": <params>}, {"penalty"}, {"mu"/"nu": <params>})``
    — or the fused single-pass core's flat dict
    ``{"mu": <params>, "nu": <params>, "count", "penalty", "gnorm"}``;
    param-shaped trees are found by the mu/nu/err path marker, everything
    else (counters, metric scalars) replicates.  The fused-kernel state
    deliberately reuses the same key names so ONE rule set covers both
    backends (asserted in tests/test_opt_step.py).
    """
    def spec_for(path, x):
        name = _leaf_name(path)
        parts = name.split("/")
        if parts[0] == "params":
            sub = path[1:]
        elif parts[0] == "ef_err":            # legacy layout
            sub = path[1:]
        elif parts[0] == "opt":
            sub = None
            for i, seg in enumerate(parts):
                if seg in _OPT_TREE_KEYS:
                    sub = path[i + 1:]
                    break
            if sub is None or x.ndim == 0:
                return NamedSharding(mesh, P())   # count, gnorm, penalty
        else:
            return NamedSharding(mesh, P())   # step, counters
        spec = fix_divisibility(
            mesh, widen_dp(mesh, param_spec(sub, x, fsdp=fsdp)), x.shape)
        return NamedSharding(mesh, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, x) for p, x in flat])


def params_shardings(mesh, param_shapes, *, fsdp: bool = True):
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes)
    return jax.tree_util.tree_unflatten(
        treedef,
        [NamedSharding(mesh, fix_divisibility(
            mesh, widen_dp(mesh, param_spec(p, x, fsdp=fsdp)), x.shape))
         for p, x in flat])


# --------------------------------------------------------------------------
# Activations / inputs
# --------------------------------------------------------------------------

def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axsize(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def data_batch_spec(mesh, batch_size: int) -> P:
    """Shard the batch dim over as many DP axes as divide it."""
    axes = []
    for a in batch_axes(mesh):
        if batch_size % (_axsize(mesh, axes + [a])) == 0:
            axes.append(a)
    return P(tuple(axes) if axes else None)


def train_batch_shardings(mesh, batch_shapes, batch_size: int):
    bspec = data_batch_spec(mesh, batch_size)

    def one(path, x):
        spec = P(*(bspec + P(*([None] * (x.ndim - 1)))))
        return NamedSharding(mesh, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shapes)
    return jax.tree_util.tree_unflatten(treedef, [one(p, x) for p, x in flat])


def cache_spec(path, x, mesh, batch: int) -> P:
    """Decode-cache sharding.

    KV tensors are (repeats, batch, len, kv_heads, head_dim): shard batch
    over the DP axes when divisible, and the *length* dim over `model`
    (+ leftover DP axes when batch is unshardable, e.g. long_500k b=1) —
    length-sharding is architecture-agnostic, unlike head-sharding which
    fails for small GQA head counts.  Recurrent states shard heads/channels
    over `model`.

    The continuous-batching slot pool is the same pytree with
    ``batch == n_slots`` (a fixed compile-time constant — DESIGN.md §7),
    so one rule set serves static and scheduled decode; quantized caches
    ride as ``k/v -> codes|scale`` children (int8, or packed-uint8 int4
    whose trailing head_dim/2 stays unsharded like head_dim).  The paged
    pool (``batch == n_blocks``) shards its block axis like a batch and
    its block's tokens like a length, in codes and scales alike.
    """
    name = _leaf_name(path)
    dims = list(x.shape)
    dp = list(batch_axes(mesh))
    used_b = []
    for a in dp:
        if batch % _axsize(mesh, used_b + [a]) == 0 and _axsize(mesh, used_b + [a]) <= batch:
            used_b.append(a)
    rest = [a for a in dp if a not in used_b]
    bspec = tuple(used_b) if used_b else None

    parts = name.split("/")
    is_kv = (parts[-1] in ("k", "v")
             or (len(parts) >= 2 and parts[-2] in ("k", "v")
                 and parts[-1] in ("codes", "scale")))
    if is_kv and x.ndim in (4, 5):
        # (r, b, len, kvh, hd-or-1); a quantized paged pool (``batch`` =
        # n_blocks) in the decode kernel's layout: codes (r, n_blocks, bs,
        # kvh*hd_c) and scales (r, n_blocks, kvh, bs) — tokens on axis 3
        tok = 3 if x.ndim == 4 and parts[-1] == "scale" else 2
        len_axes = tuple(rest) + ("model",)
        L = dims[tok]
        if L % _axsize(mesh, list(len_axes)) != 0:
            len_axes = ("model",) if L % mesh.shape["model"] == 0 else ()
        spec = [None, bspec] + [None] * (x.ndim - 2)
        spec[tok] = len_axes if len_axes else None
        return P(*spec)
    if name.endswith("ssm") and x.ndim == 5:      # (r, b, h, p, n)
        h = dims[2]
        hax = "model" if h % mesh.shape["model"] == 0 else None
        return P(None, bspec, hax, None, None)
    if name.endswith("wkv") and x.ndim == 5:      # (r, b, h, n, m)
        h = dims[2]
        hax = "model" if h % mesh.shape["model"] == 0 else None
        return P(None, bspec, hax, None, None)
    if name.endswith("conv") and x.ndim == 4:     # (r, b, k-1, conv_dim)
        c = dims[3]
        cax = "model" if c % mesh.shape["model"] == 0 else None
        return P(None, bspec, None, cax)
    if x.ndim >= 2:
        # shift states etc (r, b, d)
        return P(None, bspec)
    return P()


def cache_shardings(mesh, cache_shapes, batch: int):
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef,
        [NamedSharding(mesh, fix_divisibility(
            mesh, cache_spec(p, x, mesh, batch), x.shape)) for p, x in flat])


def activation_spec(mesh) -> P:
    """Residual-stream constraint (b, l, d): batch over DP, d over model —
    keeps the carried activations of the layer scan 2-D sharded (the
    all-gathers at matmul entry are XLA's, overlapping with compute)."""
    return P(batch_axes(mesh), None, "model")
