"""Pure-jnp oracles for every Pallas kernel in this package.

These define the exact semantics the kernels must reproduce; the test
suite sweeps shapes/dtypes and asserts allclose against them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# float32 products on the chip too: the TPU's DEFAULT precision rounds
# float32 matmul operands to bfloat16
_F32 = jax.lax.Precision.HIGHEST


def gram_ref(X, Y, *, kind="gaussian", gamma=1.0, degree=3, coef0=1.0):
    X = X.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    cross = jnp.matmul(X, Y.T, precision=_F32)  # reprolint: allow[DET01] bulk oracle, compared under PARITY_RTOL
    if kind == "linear":
        return cross
    if kind == "poly":
        return (cross + coef0) ** degree
    xx = jnp.sum(X * X, axis=-1)[:, None]
    yy = jnp.sum(Y * Y, axis=-1)[None, :]
    sq = jnp.maximum(xx + yy - 2.0 * cross, 0.0)
    return jnp.exp(-gamma * sq)


def rff_ref(X, W, b, *, num_features=None):
    X = X.astype(jnp.float32)
    W = W.astype(jnp.float32)
    D = num_features or W.shape[0]
    # reprolint: allow[DET01] bulk oracle, compared under PARITY_RTOL
    return jnp.sqrt(2.0 / D) * jnp.cos(jnp.matmul(X, W.T, precision=_F32)
                                       + b.astype(jnp.float32)[None, :])


def quadform_ref(X, Y, alpha, beta, *, kind="gaussian", gamma=1.0,
                 degree=3, coef0=1.0):
    K = gram_ref(X, Y, kind=kind, gamma=gamma, degree=degree, coef0=coef0)
    # reprolint: allow[DET01] bulk oracle, compared under PARITY_RTOL
    return jnp.matmul(jnp.matmul(alpha.astype(jnp.float32), K, precision=_F32),
                      beta.astype(jnp.float32), precision=_F32)


def sv_predict_ref(X, SV, A, *, kind="gaussian", gamma=1.0, degree=3,
                   coef0=1.0):
    """Masked batched SV predictions: yhat_i = sum_j k(X_i, SV_ij) A_ij.

    X (B, d), SV (B, N, d), A (B, N); padded support slots must carry
    zero coefficients (that is the masking contract — k(x, 0) is
    multiplied by 0, never looked at)."""

    def one(x, S, a):
        # multiply + sum, not `@`: the serving predict path is under the
        # bitwise contract, so the oracle pins the same reduction order
        # as rkhs.predict (DESIGN.md Sec. 9).
        k = gram_ref(x[None, :], S, kind=kind, gamma=gamma,
                     degree=degree, coef0=coef0)[0]
        return jnp.sum(k * a.astype(jnp.float32))

    return jax.vmap(one)(X, SV, A)


def _loss_grad_ref(loss, yhat, y):
    if loss == "hinge":
        ell = jnp.maximum(0.0, 1.0 - y * yhat)
        return ell, jnp.where(ell > 0.0, -y, 0.0)
    r = yhat - y
    return 0.5 * r * r, r


def primal_step_ref(X, Yl, w, b, *, W=None, bias=None, scale=1.0,
                    loss="hinge", eta=0.5, lam=0.01):
    """Oracle for fused.primal_step_pallas: one online round for B
    stacked primal learners -> (w_new, b_new, ell, yhat)."""
    X = X.astype(jnp.float32)
    Yl = Yl.astype(jnp.float32)
    w = w.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if W is not None:
        # reprolint: allow[DET01] bulk oracle, compared under PARITY_RTOL
        z = scale * jnp.cos(jnp.matmul(X, W.T.astype(jnp.float32),
                                       precision=_F32)
                            + bias.astype(jnp.float32)[None, :])
    else:
        z = X
    yhat = jnp.sum(w * z, axis=-1) + b
    ell, g = _loss_grad_ref(loss, yhat, Yl)
    w_new = (1.0 - eta * lam) * w - eta * g[:, None] * z
    b_new = b - eta * g
    return w_new, b_new, ell, yhat
