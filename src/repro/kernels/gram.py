"""Blocked Gram-matrix Pallas kernel (TPU target).

The compute hot-spot of the paper's kernel learners is Gram algebra:
predictions K(X, S) @ alpha, RKHS norms alpha^T K alpha, and the
divergence/local-condition distances — all dominated by pairwise kernel
evaluations.  A GPU implementation would assign one row per thread; on
TPU we instead block for the MXU:

  K[i, j] = exp(-gamma * (||x_i||^2 + ||y_j||^2 - 2 x_i . y_j))

- the cross term -2 X Y^T is a (bm x d) @ (d x bn) matmul on the MXU,
  accumulated in fp32 via preferred_element_type;
- the row/col squared norms are computed in-block on the VPU and fused
  with the exponential, so the intermediate squared-distance matrix
  never leaves VMEM;
- block sizes default to 128/256 — MXU-aligned (multiples of 128 on the
  contracted and output dims; inputs are zero-padded to alignment by
  ops.py, which is exact for the cross term and masked for the norms).

Grid: (ceil(M/bm), ceil(N/bn)); each program writes one (bm, bn) output
tile.  The feature dim d is kept whole inside the block (kernel-method
d is small — tens to a few hundred — so a (bm, d) slab fits VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BN = 128


def _gram_kernel(x_ref, y_ref, o_ref, *, kind: str, gamma: float,
                 degree: int, coef0: float):
    """One (bm, bn) tile of the Gram matrix."""
    x = x_ref[...].astype(jnp.float32)           # (bm, d)
    y = y_ref[...].astype(jnp.float32)           # (bn, d)
    cross = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                            # (bm, bn) on the MXU
    if kind == "linear":
        o_ref[...] = cross
    elif kind == "poly":
        o_ref[...] = (cross + coef0) ** degree
    else:  # gaussian
        xx = jnp.sum(x * x, axis=1, keepdims=True)       # (bm, 1)
        yy = jnp.sum(y * y, axis=1, keepdims=True).T     # (1, bn)
        sq = jnp.maximum(xx + yy - 2.0 * cross, 0.0)
        o_ref[...] = jnp.exp(-gamma * sq)


def gram_pallas(
    X: jnp.ndarray,
    Y: jnp.ndarray,
    *,
    kind: str = "gaussian",
    gamma: float = 1.0,
    degree: int = 3,
    coef0: float = 1.0,
    block_m: int = DEFAULT_BM,
    block_n: int = DEFAULT_BN,
    interpret: bool = False,
) -> jnp.ndarray:
    """K(X, Y) with X: (M, d), Y: (N, d).  M, N, d must already be
    padded to block multiples (ops.py handles padding + masking)."""
    M, d = X.shape
    N, _ = Y.shape
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)

    kernel = functools.partial(
        _gram_kernel, kind=kind, gamma=gamma, degree=degree, coef0=coef0
    )
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(X, Y)
