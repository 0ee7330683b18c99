"""Random Fourier Features learner (paper Sec. 4, 'future work').

The paper notes that a finite-dimensional approximation of the feature
map (Rahimi & Recht 2007) would give kernel-quality models with
*linear-model communication*: the model is a fixed-size primal weight
vector over D random features, so a synchronization transmits O(m D)
bytes regardless of how many examples have been seen — the strict
adaptivity of Cor. 8 applies verbatim.

phi(x) = sqrt(2/D) * cos(W x + b),   W ~ N(0, 2*gamma I),  b ~ U[0, 2pi]

approximates the Gaussian kernel k(x, y) = exp(-gamma ||x-y||^2) via
E[phi(x).phi(y)] = k(x, y).

This module provides the feature map (the Pallas-fused path lives in
repro.kernels.ops.rff_features) and the RFF learner state.  Protocol
integration — the scan engine, the async runtime, sweeps, and the
Sec. 3 byte accounting — goes through ``substrate.RFFSubstrate``
(DESIGN.md Sec. 8), which closes the paper's open question empirically
(benchmarks/bench_rff.py).  ``make_update`` stays as the standalone
reference update the substrate is tested against.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .rkhs import rowsum

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class RFFSpec:
    dim: int            # input dim d
    num_features: int   # D
    gamma: float = 1.0
    seed: int = 0


def rff_params(spec: RFFSpec) -> Tuple[Array, Array]:
    kw, kb = jax.random.split(jax.random.PRNGKey(spec.seed))
    W = jax.random.normal(kw, (spec.num_features, spec.dim)) * jnp.sqrt(2.0 * spec.gamma)
    b = jax.random.uniform(kb, (spec.num_features,), maxval=2.0 * jnp.pi)
    return W, b


def featurize(spec: RFFSpec, W: Array, b: Array, X: Array) -> Array:
    """phi(X): (..., d) -> (..., D).  Pure-jnp reference; see
    repro.kernels.ops.rff_features for the Pallas path.

    The projection is an explicit multiply + fixed-order last-axis sum
    (``rkhs.rowsum``) rather than ``X @ W.T``: a row's result is then
    independent of how many rows share the call, which is what lets
    the mesh-sharded engine
    (one learner slice per device) reproduce the single-device engine
    bit-for-bit (DESIGN.md Sec. 9 — XLA's gemm kernels pick
    row-count-dependent accumulation orders, gemv vs gemm).  The
    materialized (..., D, d) intermediate is small at simulation scale;
    the Pallas path owns the large-D regime.
    """
    lead = tuple(range(X.ndim - 1))     # explicit broadcast of the
    Wx = jnp.expand_dims(W, lead)       # (D, d) params over X's batch
    bx = jnp.expand_dims(b, lead)       # axes (rank promotion is off)
    proj = rowsum(X[..., None, :] * Wx) + bx
    return jnp.sqrt(2.0 / spec.num_features) * jnp.cos(proj)


class RFFLearnerState(NamedTuple):
    w: Array   # (D,) primal weights
    b: Array   # ()


def init_state(spec: RFFSpec) -> RFFLearnerState:
    return RFFLearnerState(
        w=jnp.zeros((spec.num_features,), jnp.float32), b=jnp.zeros((), jnp.float32)
    )


def make_update(spec: RFFSpec, W: Array, bias: Array, *, eta: float = 0.5,
                lam: float = 0.01, loss: str = "hinge"):
    """SGD in the RFF primal space — an exactly loss-proportional convex
    update on a fixed-size model."""

    def update(state: RFFLearnerState, example):
        x, y = example
        z = featurize(spec, W, bias, x[None])[0]
        yhat = rowsum(state.w * z) + state.b
        if loss == "hinge":
            ell = jnp.maximum(0.0, 1.0 - y * yhat)
            g = jnp.where(ell > 0, -y, 0.0)
        else:
            r = yhat - y
            ell, g = 0.5 * r * r, r
        w = (1.0 - eta * lam) * state.w - eta * g * z
        b = state.b - eta * g
        return RFFLearnerState(w=w, b=b), ell

    return update
