"""Fused per-round Pallas kernels (TPU target; DESIGN.md Sec. 12).

The per-round hot path of every substrate family, as ONE kernel each:

- :func:`sv_predict_pallas` — the SV family's round is dominated by
  evaluating the support-vector expansion f_i(x_i) = sum_j k(x_i,
  s_ij) a_ij for each of the B stacked learners.  Composing the
  seed-era ``gram`` + a contraction materializes a (B, N) kernel-row
  matrix in HBM only to immediately reduce it; this kernel fuses the
  Gram tile, the masked-coefficient product, and the reduction so only
  the (B,) predictions leave VMEM.  Masking rides in the coefficients:
  ops.py zeroes the alpha entries of padded sorted-id slots, so padded
  support vectors contribute exactly 0 no matter what k(x, 0) is.

- :func:`primal_step_pallas` — the RFF/linear families' ENTIRE round
  (featurize + predict-dot + loss/grad + SGD update) in one launch:
  z = sqrt(2/D) cos(W x + b) on the MXU+VPU, yhat = <w, z> + b, the
  hinge (or squared) loss and its grad, and the NORMA-decayed weight
  update — the pre-activation matrix, the feature matrix, and the
  gradient never round-trip to HBM.  With ``featurize=False`` the
  identity feature map makes it the linear learner's fused round.

Both kernels block only axes whose accumulation stays row-local, so a
row's floats never depend on how many rows share the launch — the
predict_batch bit-exactness contract (core/substrate.py) extends to
the fused path by construction.

Inputs arrive pre-padded to block multiples (ops.py pads and crops,
exactly like the seed-era kernels, and launches with 128-wide tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 128
DEFAULT_BM = 128
_LANE = 128          # TPU lane width: lane-dense output rows


def _kernel_row(x, sv, *, kind: str, gamma: float, degree: int,
                coef0: float) -> jnp.ndarray:
    """k(x, sv): x (1, d), sv (bn, d) -> (1, bn), fp32 on the MXU."""
    cross = jax.lax.dot_general(
        x, sv, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                              # (1, bn)
    if kind == "linear":
        return cross
    if kind == "poly":
        return (cross + coef0) ** degree
    xx = jnp.sum(x * x, axis=1, keepdims=True)     # (1, 1)
    yy = jnp.sum(sv * sv, axis=1, keepdims=True).T  # (1, bn)
    return jnp.exp(-gamma * jnp.maximum(xx + yy - 2.0 * cross, 0.0))


def _sv_predict_kernel(x_ref, sv_ref, a_ref, o_ref, *, kind: str,
                       gamma: float, degree: int, coef0: float):
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)               # (1, d)
    sv = sv_ref[0].astype(jnp.float32)             # (bn, d)
    a = a_ref[0].astype(jnp.float32)               # (1, bn)
    k = _kernel_row(x, sv, kind=kind, gamma=gamma, degree=degree,
                    coef0=coef0)
    partial_val = jnp.sum(k * a, axis=1, keepdims=True)   # (1, 1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Mosaic stores vectors only: every lane of the (1, LANE) output row
    # carries the same running sum, and the wrapper reads lane 0.
    o_ref[0] += partial_val


def sv_predict_pallas(
    X: jnp.ndarray,       # (B, d)    one query per stacked learner
    SV: jnp.ndarray,      # (B, N, d) stacked support sets (padded)
    A: jnp.ndarray,       # (B, N)    coefficients, padded slots zeroed
    *,
    kind: str = "gaussian",
    gamma: float = 1.0,
    degree: int = 3,
    coef0: float = 1.0,
    block_n: int = DEFAULT_BN,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B,) fused masked predictions; N, d pre-padded (N % block_n
    == 0).  Grid (B, N/bn): the budget axis streams through VMEM and
    accumulates into one value per learner — rows are independent
    grid cells, so per-row floats don't depend on B.

    X and A travel as (B, 1, ·) so that the last two dims of every
    block either equal the array's or are (8, 128)-aligned, which the
    TPU lowering requires; the output is a lane-dense (B, 1, LANE)
    slab for the same reason.
    """
    B, N, d = SV.shape
    assert X.shape == (B, d) and A.shape == (B, N), (X.shape, SV.shape,
                                                     A.shape)
    assert N % block_n == 0, (N, block_n)
    kernel = functools.partial(
        _sv_predict_kernel, kind=kind, gamma=gamma, degree=degree,
        coef0=coef0)
    out = pl.pallas_call(
        kernel,
        grid=(B, N // block_n),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_n, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANE), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, _LANE), jnp.float32),
        interpret=interpret,
    )(X.reshape(B, 1, d), SV, A.reshape(B, 1, N))
    return out[:, 0, 0]


def _loss_grad(loss: str, yhat, y):
    """(ell, dell/dyhat) — the same formulas as core.learners
    .loss_and_grad, restated here so kernels stay core-independent."""
    if loss == "hinge":
        ell = jnp.maximum(0.0, 1.0 - y * yhat)
        return ell, jnp.where(ell > 0.0, -y, 0.0)
    r = yhat - y
    return 0.5 * r * r, r


def _primal_step_math(z, w, b_row, y_row, *, loss: str, eta: float,
                      lam: float):
    """The shared round math on a (bm, D) feature block: returns
    (w_new, b_new_row, ell_row, yhat_row) with the *_row values shaped
    (1, bm)."""
    yhat = jnp.sum(w * z, axis=1)[None, :] + b_row  # (1, bm)
    ell, g = _loss_grad(loss, yhat, y_row)
    w_new = (1.0 - eta * lam) * w - eta * g.T * z   # g.T: (bm, 1)
    b_new = b_row - eta * g
    return w_new, b_new, ell, yhat


def _rff_step_kernel(x_ref, y_ref, w_ref, b_ref, wf_ref, bias_ref,
                     ow_ref, ob_ref, oell_ref, oyh_ref, *, scale: float,
                     loss: str, eta: float, lam: float):
    x = x_ref[...].astype(jnp.float32)              # (bm, d)
    wf = wf_ref[...].astype(jnp.float32)            # (D, d)
    bias = bias_ref[...].astype(jnp.float32)        # (1, D)
    proj = jax.lax.dot_general(
        x, wf, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                               # (bm, D) on the MXU
    z = scale * jnp.cos(proj + bias)
    w_new, b_new, ell, yhat = _primal_step_math(
        z, w_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32), loss=loss, eta=eta, lam=lam)
    ow_ref[...] = w_new
    ob_ref[...] = b_new
    oell_ref[...] = ell
    oyh_ref[...] = yhat


def _linear_step_kernel(x_ref, y_ref, w_ref, b_ref, ow_ref, ob_ref,
                        oell_ref, oyh_ref, *, loss: str, eta: float,
                        lam: float):
    w_new, b_new, ell, yhat = _primal_step_math(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32), y_ref[...].astype(jnp.float32),
        loss=loss, eta=eta, lam=lam)
    ow_ref[...] = w_new
    ob_ref[...] = b_new
    oell_ref[...] = ell
    oyh_ref[...] = yhat


def primal_step_pallas(
    X: jnp.ndarray,        # (B, d)  one example per stacked learner
    Yl: jnp.ndarray,       # (B,)    labels
    w: jnp.ndarray,        # (B, D)  stacked weights
    b: jnp.ndarray,        # (B,)    stacked biases
    *,
    W: jnp.ndarray | None = None,      # (D, d) RFF projection, or None
    bias: jnp.ndarray | None = None,   # (D,)   RFF phases
    scale: float = 1.0,                # sqrt(2 / num_features)
    loss: str = "hinge",
    eta: float = 0.5,
    lam: float = 0.01,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
):
    """One fused online round for B stacked primal learners: returns
    (w_new (B, D), b_new (B,), ell (B,), yhat (B,)).

    The learner axis B is the only blocked axis (B % block_m == 0,
    pre-padded); the feature axis D stays whole per program so the
    predict reduction and the update see the full feature row in VMEM
    — which bounds D by VMEM (a (bm, D) fp32 slab; ~2k features at
    bm = 128 uses ~1 MB) and is exactly the regime the paper's RFF
    models live in.  With ``W``/``bias`` set the feature map runs
    in-kernel; otherwise z = x (linear family).
    """
    B, d = X.shape
    D = w.shape[1]
    assert B % block_m == 0, (B, block_m)
    assert w.shape == (B, D) and Yl.shape == (B,) and b.shape == (B,)
    featurize = W is not None
    y_row = Yl.reshape(1, B)
    b_row = b.reshape(1, B)
    row = lambda i: (0, i)                 # (1, bm) blocks over the B axis
    slab = lambda i: (i, 0)                # (bm, ·) blocks over the B axis
    row_specs = pl.BlockSpec((1, block_m), row)
    out_shapes = (
        jax.ShapeDtypeStruct((B, D), jnp.float32),   # w_new
        jax.ShapeDtypeStruct((1, B), jnp.float32),   # b_new
        jax.ShapeDtypeStruct((1, B), jnp.float32),   # ell
        jax.ShapeDtypeStruct((1, B), jnp.float32),   # yhat
    )
    out_specs = (pl.BlockSpec((block_m, D), slab), row_specs, row_specs,
                 row_specs)
    if featurize:
        assert W.shape == (D, d) and bias is not None and bias.shape == (D,)
        kernel = functools.partial(
            _rff_step_kernel, scale=scale, loss=loss, eta=eta, lam=lam)
        in_specs = [
            pl.BlockSpec((block_m, d), slab),        # X
            row_specs,                               # labels
            pl.BlockSpec((block_m, D), slab),        # w
            row_specs,                               # b
            pl.BlockSpec((D, d), lambda i: (0, 0)),  # W (whole)
            pl.BlockSpec((1, D), lambda i: (0, 0)),  # bias (whole)
        ]
        args = (X, y_row, w, b_row, W, bias.reshape(1, D))
    else:
        assert D == d, (D, d)
        kernel = functools.partial(
            _linear_step_kernel, loss=loss, eta=eta, lam=lam)
        in_specs = [
            pl.BlockSpec((block_m, d), slab),
            row_specs,
            pl.BlockSpec((block_m, D), slab),
            row_specs,
        ]
        args = (X, y_row, w, b_row)
    w_new, b_new, ell, yhat = pl.pallas_call(
        kernel,
        grid=(B // block_m,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)
    return w_new, b_new[0], ell[0], yhat[0]
