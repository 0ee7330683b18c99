"""Fused RKHS quadratic-form Pallas kernel (TPU target).

q = alpha^T K(X, Y) beta  —  the building block of RKHS norms,
distances, and the divergence/local-condition monitoring (Sec. 2).

A naive implementation materializes the (M, N) Gram matrix in HBM only
to immediately contract it on both sides.  This kernel streams (bm, bn)
Gram tiles through VMEM and accumulates the scalar

    q = sum_ij alpha_i K_ij beta_j

in an fp32 accumulator, so HBM traffic is O(M d + N d) instead of
O(M N) — on a v5e (819 GB/s HBM) this turns the divergence check from
memory-bound to compute-bound for typical budgets.

TPU grid iterations execute sequentially, so cross-step accumulation
into the output ref is safe; the first step initializes it.  The
accumulator is a lane-dense (1, 128) row, not a (1, 1) scalar: the
TPU lowering cannot store a scalar to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BN = 128
_LANE = 128          # TPU lane width: lane-dense output row


def _quadform_kernel(x_ref, y_ref, a_ref, b_ref, o_ref, *, kind: str,
                     gamma: float, degree: int, coef0: float):
    i = pl.program_id(0)
    j = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)            # (bm, d)
    y = y_ref[...].astype(jnp.float32)            # (bn, d)
    a = a_ref[...].astype(jnp.float32)            # (1, bm)
    b = b_ref[...].astype(jnp.float32)            # (1, bn)

    cross = jax.lax.dot_general(
        x, y, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if kind == "linear":
        k = cross
    elif kind == "poly":
        k = (cross + coef0) ** degree
    else:
        xx = jnp.sum(x * x, axis=1, keepdims=True)
        yy = jnp.sum(y * y, axis=1, keepdims=True).T
        k = jnp.exp(-gamma * jnp.maximum(xx + yy - 2.0 * cross, 0.0))

    partial_val = jnp.sum((a.T * k) * b)          # alpha_i K_ij beta_j over tile

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Mosaic stores vectors only: every lane of the (1, LANE) output
    # carries the same running sum, and the wrapper reads lane 0.
    o_ref[...] += partial_val


def quadform_pallas(
    X: jnp.ndarray,      # (M, d)
    Y: jnp.ndarray,      # (N, d)
    alpha: jnp.ndarray,  # (M,)
    beta: jnp.ndarray,   # (N,)
    *,
    kind: str = "gaussian",
    gamma: float = 1.0,
    degree: int = 3,
    coef0: float = 1.0,
    block_m: int = DEFAULT_BM,
    block_n: int = DEFAULT_BN,
    interpret: bool = False,
) -> jnp.ndarray:
    M, d = X.shape
    N, _ = Y.shape
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    kernel = functools.partial(
        _quadform_kernel, kind=kind, gamma=gamma, degree=degree, coef0=coef0
    )
    out = pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, _LANE), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, _LANE), jnp.float32),
        interpret=interpret,
    )(X, Y, alpha.reshape(1, M), beta.reshape(1, N))
    return out[0, 0]
