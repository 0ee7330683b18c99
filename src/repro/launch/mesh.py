"""Production mesh construction.

Single pod:  (data=16, model=16)          = 256 chips (v5e pod)
Multi-pod:   (pod=2, data=16, model=16)   = 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see the
default single CPU device).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the repo places
    arrays with explicit NamedShardings and shard_map specs and lets the
    compiler propagate the rest (jax >= 0.7 defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host/CPU) devices exist — used by
    the distributed-protocol integration tests."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def make_learner_mesh(n: int = 0):
    """1-D mesh with the ``learners`` axis over n devices (default:
    all available) — the axis the mesh-sharded scan engine shards the
    m-learner dim over (``engine.run(..., mesh=...)``, DESIGN.md
    Sec. 9).  The learner count m must divide evenly over n."""
    if n == 0:
        n = len(jax.devices())
    return _auto_mesh((n,), ("learners",))


def data_axes(mesh) -> Tuple[str, ...]:
    """The learner/batch axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def num_learners(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
