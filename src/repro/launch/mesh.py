"""Mesh construction: the one helper every launcher, benchmark and test
builds its mesh through.

``make_mesh`` gives every axis ``AxisType.Auto``: the model code places
arrays with ``with_sharding_constraint`` and lets GSPMD propagate, which
is what Auto axes mean.  (``jax.make_mesh`` defaults to ``Explicit`` axes
on current JAX, under which the model's constraints and gathers raise
``ShardingTypeError``.)

Single pod: (16, 16) = 256 chips, axes (data, model).  Multi-pod:
(2, 16, 16) = 512 chips, axes (pod, data, model) — ``pod`` is an outer
data-parallel axis whose collectives cross the inter-pod (DCN) link.
Importing this module never touches jax device state.

The dry-run entrypoint (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing
jax; nothing else in the repo does, so tests and benchmarks see the real
single CPU device.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (per-direction, approx)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """Mesh of ``shape`` over ``axes`` (all ``AxisType.Auto``), on
    ``devices`` (default: the first ``prod(shape)`` of ``jax.devices()``)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_from_arg(spec: Optional[str]):
    """A launcher's ``--mesh`` value: ``"2x4"`` is (data, model),
    ``"2x2x2"`` is (pod, data, model); ``None`` is the 1x1 mesh."""
    if not spec:
        return make_mesh((1, 1), ("data", "model"))
    shape = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    return make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod is outer DP)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
