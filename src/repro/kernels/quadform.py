"""Fused RKHS quadratic-form Pallas kernel (TPU target).

q = alpha^T K(X, Y) beta  —  the building block of RKHS norms,
distances, and the divergence/local-condition monitoring (Sec. 2).

The (M, N) Gram matrix is never written to HBM: it is evaluated in
VMEM and contracted on both sides at once, so HBM traffic is the
operands alone, O((M + N) d).

Layout.  The slot axes lie on the lanes: the kernel reads X^T (d, M),
Y^T (d, N), alpha (1, M) and beta (1, N), with d unpadded (a block
dim equal to the array's own is legal in Mosaic).  A learner's
(512, 8) expansion is then 16 KB in HBM, not a (512, 128) pad of
256 KB, and for the stacked learners XLA lays the (m, tau, d) stack
out so that the transpose is free.  The slot axes arrive padded to a
multiple of 128 with zero coefficients (ops.py).

Blocking.  A grid step takes the whole padded extents up to
``_BLOCK`` slots a side, so one learner's 512-slot check is one step;
a vmap over learners adds a leading grid axis of one step per
learner.  Within a step the X block is transposed once into VMEM
scratch (rows on sublanes), and the Gram is walked in row strips
against every column of the block: alpha_i K_ij accumulates
elementwise into one (strip, bn) value, which is reduced against beta
to a scalar once at the end of the step.  VMEM holds a block and a
strip, never the Gram.

The cross term is d unrolled multiply-adds on the vector unit (the
Gaussian kernel sums squared differences), exact float32 and
elementwise, so a learner's floats are the same in a vmapped and an
unbatched launch on every backend.  ``_STRIP`` rows a strip was chosen
with tools/quadform_cost.py, which times the strip sizes on a chip.

A row's floats depend only on its own learner's operands and the
grid order within that learner, never on how many learners share the
launch or on which operand is shared (``SVSubstrate.dist_to_ref``
against ``dist_to_ref_each``).  TPU grid iterations run sequentially,
so steps accumulate into the output ref; the first step initializes
it.  The output is a lane-dense (1, 128) row, not a (1, 1) scalar:
the TPU lowering cannot store a scalar to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128          # TPU lane width: lane-dense output row
_BLOCK = 512         # largest slot block a side of one grid step
_STRIP = 32          # Gram rows a strip; divides every slot block


def _slot_block(n: int) -> int:
    """The whole padded extent up to ``_BLOCK``, else the largest
    multiple of 128 up to ``_BLOCK`` that divides it."""
    if n <= _BLOCK:
        return n
    return max(b for b in range(_LANE, _BLOCK + 1, _LANE) if n % b == 0)


def _strip_values(x, yt, *, kind: str, gamma: float, degree: int,
                  coef0: float):
    """k(x_i, y_j) for a strip: x (s, d), yt (d, bn) -> (s, bn)."""
    acc = None
    for k in range(x.shape[1]):
        xk, yk = x[:, k:k + 1], yt[k:k + 1, :]
        term = (xk - yk) ** 2 if kind == "gaussian" else xk * yk
        acc = term if acc is None else acc + term
    if kind == "gaussian":
        return jnp.exp(-gamma * acc)
    if kind == "linear":
        return acc
    return (acc + coef0) ** degree


def _quadform_kernel(xt_ref, yt_ref, a_ref, b_ref, o_ref, x_scr, a_scr, *,
                     kind: str, gamma: float, degree: int, coef0: float):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bn = x_scr.shape[0], yt_ref.shape[1]
    x_scr[...] = xt_ref[...].astype(jnp.float32).T   # (bm, d)
    a_scr[...] = a_ref[...].astype(jnp.float32).T    # (bm, 1)
    yt = yt_ref[...].astype(jnp.float32)             # (d, bn)

    def row_strip(s, acc):
        rows = pl.ds(pl.multiple_of(s * _STRIP, _STRIP), _STRIP)
        k = _strip_values(x_scr[rows, :], yt, kind=kind, gamma=gamma,
                          degree=degree, coef0=coef0)
        return acc + a_scr[rows, :] * k

    acc = jax.lax.fori_loop(0, bm // _STRIP, row_strip,
                            jnp.zeros((_STRIP, bn), jnp.float32))
    b = b_ref[...].astype(jnp.float32)
    # Mosaic stores vectors only: every lane of the (1, LANE) output
    # carries the same running sum, and the wrapper reads lane 0.
    o_ref[...] += jnp.sum(jnp.sum(acc, axis=0, keepdims=True) * b)


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
    interpret: bool = False,
) -> jnp.ndarray:
    """alpha^T K(X, Y) beta; M and N pre-padded to multiples of 128
    with zero alpha/beta entries, d unpadded."""
    M, d = X.shape
    N, _ = Y.shape
    assert M % _LANE == 0 and N % _LANE == 0, (M, N)
    bm, bn = _slot_block(M), _slot_block(N)
    kernel = functools.partial(
        _quadform_kernel, kind=kind, gamma=gamma, degree=degree, coef0=coef0
    )
    out = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((d, bm), lambda i, j: (0, i)),
            pl.BlockSpec((d, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bm), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, _LANE), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, _LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(X.T, Y.T, alpha.reshape(1, M), beta.reshape(1, N))
    return out[0, 0]
