"""Public wrappers around the Pallas kernels.

Each public op pads its operands, launches its kernel and crops the
result; it does not decide whether a kernel should run.  That decision
belongs to the caller: ``core.substrate`` launches an op only when
``engages`` holds for the extents it blocks, and otherwise runs its own
reference expressions (DESIGN.md Sec. 12).  ``rkhs_dist_sq`` is the one
op that chooses, per quadratic-form term, because its terms' extents
can fall on both sides of the threshold.

- padding: every blocked axis is padded to a multiple of ``_LANE``
  (128), and every kernel launches with 128-wide tiles.  Zero padding
  is exact for the feature dim of every kernel kind; padded rows/cols
  are cropped from outputs, and padded alpha/beta entries are zero so
  quadform is exact.
- interpret mode off-TPU: on a CPU backend interpret=True executes the
  kernel bodies in Python, which is how the test suite checks the TPU
  kernels' numerics; on a TPU backend they compile to Mosaic
  (tests/test_tpu_compile.py compiles them for a described v5e).

Each public op ticks ``TRACE_COUNTS`` and calls a module-level jitted
launcher whose static arguments are the kernel's own parameters.  The
SV ops take a ``spec`` duck-typed against ``core.rkhs.KernelSpec``
(kind / gamma / degree / coef0), so this package stays
import-independent of core.

``TRACE_COUNTS`` ticks once per *trace* of a Pallas launch site (per
call when eager; once per compile inside an outer jit, however many
times the compiled program then launches the kernel) — the path-proof
used by the backend-parity tests and the serving
``bucket_predict_hits_pallas`` claim: parity says the numbers match,
the counter says the fused kernel actually produced them.  Device
launches are read from a profiler trace, not from here
(``chipbench/trace.py``).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import ref
from .fused import primal_step_pallas, sv_predict_pallas
from .gram import gram_pallas
from .quadform import quadform_pallas
from .rff import rff_pallas

_LANE = 128          # TPU lane width: padding multiple and tile width
_MIN_PALLAS = 128    # below this, callers run the jnp reference

TRACE_COUNTS: collections.Counter = collections.Counter()

# The backend-parity tolerance: one pinned pair for every pallas-vs-
# reference comparison (tests/conftest.py, chip_smoke.py).  The kernels
# accumulate in fp32 with a tile order that differs from the jnp
# oracles, so values agree to a few ULP-amplified rounding steps; rtol
# covers the large-magnitude RKHS distances, atol the near-zero hinge
# margins.
PARITY_RTOL = 1e-3
PARITY_ATOL = 5e-3


def engages(*dims) -> bool:
    """True when these operand extents take the Pallas branch.

    The single threshold every caller shares: a launch engages when any
    blocked extent reaches ``_MIN_PALLAS``.  The substrate layer keys
    its backend dispatch on this, so "pallas backend, tiny model" runs
    the reference expressions bit-for-bit.
    """
    return max(int(d) for d in dims) >= _MIN_PALLAS


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _kernel_kw(spec) -> dict:
    return dict(kind=spec.kind, gamma=spec.gamma, degree=spec.degree,
                coef0=spec.coef0)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    target = ((n + mult - 1) // mult) * mult
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# Jitted launchers (pad -> pallas_call -> crop, all inside one trace)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "interpret"),
)
def _gram_call(X, Y, *, kind, gamma, degree, coef0, interpret):
    M, N = X.shape[0], Y.shape[0]
    Xp = _pad_to(_pad_to(X, 0, _LANE), 1, _LANE)
    Yp = _pad_to(_pad_to(Y, 0, _LANE), 1, _LANE)
    K = gram_pallas(
        Xp, Yp, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_m=_LANE, block_n=_LANE, interpret=interpret,
    )
    return K[:M, :N]


@functools.partial(jax.jit, static_argnames=("num_features", "interpret"))
def _rff_call(X, W, b, *, num_features, interpret):
    M, D = X.shape[0], W.shape[0]
    Xp = _pad_to(_pad_to(X, 0, _LANE), 1, _LANE)
    Wp = _pad_to(_pad_to(W, 0, _LANE), 1, _LANE)
    bp = _pad_to(b, 0, _LANE)
    Z = rff_pallas(
        Xp, Wp, bp, num_features=num_features, block_m=_LANE,
        block_d=_LANE, interpret=interpret,
    )
    return Z[:M, :D]


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "interpret"),
)
def _quadform_call(X, Y, alpha, beta, *, kind, gamma, degree, coef0,
                   interpret):
    Xp = _pad_to(_pad_to(X, 0, _LANE), 1, _LANE)
    Yp = _pad_to(_pad_to(Y, 0, _LANE), 1, _LANE)
    ap = _pad_to(alpha, 0, _LANE)
    bp = _pad_to(beta, 0, _LANE)
    return quadform_pallas(
        Xp, Yp, ap, bp, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_m=_LANE, block_n=_LANE, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "interpret"),
)
def _sv_predict_call(X, SV, A, *, kind, gamma, degree, coef0, interpret):
    Xp = _pad_to(X, 1, _LANE)
    SVp = _pad_to(_pad_to(SV, 1, _LANE), 2, _LANE)
    Ap = _pad_to(A, 1, _LANE)
    return sv_predict_pallas(
        Xp, SVp, Ap, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_n=_LANE, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "loss", "eta", "lam", "featurize",
                     "interpret"),
)
def _primal_step_call(X, Yl, w, b, W, bias, *, scale, loss, eta, lam,
                      featurize, interpret):
    B, D = w.shape
    Xp = _pad_to(_pad_to(X, 0, _LANE), 1, _LANE)
    wp = _pad_to(_pad_to(w, 0, _LANE), 1, _LANE)
    yp = _pad_to(Yl, 0, _LANE)
    bp = _pad_to(b, 0, _LANE)
    if featurize:
        # Padding the feature axis D makes the extra z columns
        # cos(0 + 0) = 1 (not 0) — harmless: the matching w columns are
        # zero-padded, so yhat is exact, and the garbage w_new columns
        # are cropped right here.
        Wp = _pad_to(_pad_to(W, 0, _LANE), 1, _LANE)
        biasp = _pad_to(bias, 0, _LANE)
    else:
        Wp, biasp = None, None
    w_new, b_new, ell, yhat = primal_step_pallas(
        Xp, yp, wp, bp, W=Wp, bias=biasp, scale=scale, loss=loss,
        eta=eta, lam=lam, block_m=_LANE, interpret=interpret,
    )
    return w_new[:B, :D], b_new[:B], ell[:B], yhat[:B]


# ---------------------------------------------------------------------------
# Public ops
# ---------------------------------------------------------------------------


def gram(spec, X, Y):
    """K(X, Y): (M, d), (N, d) -> (M, N) fp32."""
    TRACE_COUNTS["gram"] += 1
    return _gram_call(X, Y, **_kernel_kw(spec), interpret=_interpret())


def rff_features(X, W, b, *, num_features=None):
    """phi(X): (M, d) with W (D, d), b (D,) -> (M, D) fp32."""
    TRACE_COUNTS["rff"] += 1
    return _rff_call(X, W, b, num_features=num_features or W.shape[0],
                     interpret=_interpret())


def quadform(spec, X, Y, alpha, beta):
    """alpha^T K(X, Y) beta -> scalar fp32, without materializing K in HBM."""
    TRACE_COUNTS["quadform"] += 1
    return _quadform_call(X, Y, alpha, beta, **_kernel_kw(spec),
                          interpret=_interpret())


def rkhs_dist_sq(spec, X, Y, alpha, beta):
    """||f - g||_H^2 via three quadratic forms (never materializes any
    Gram matrix in HBM) — the divergence-monitoring hot path.

    Each term launches the fused ``quadform`` only when its own extents
    engage; a term below the threshold (a sync budget under 128 beside
    a learner budget above it) runs ``ref.quadform_ref``."""
    def term(P, Q, p, q):
        if engages(P.shape[0], Q.shape[0]):
            return quadform(spec, P, Q, p, q)
        return ref.quadform_ref(P, Q, p, q, **_kernel_kw(spec))

    return (
        term(X, X, alpha, alpha)
        + term(Y, Y, beta, beta)
        - 2.0 * term(X, Y, alpha, beta)
    )


def sv_predict(spec, X, SV, A):
    """Fused batched SV predictions: yhat_i = sum_j k(X_i, SV_ij) A_ij.

    X (B, d), SV (B, N, d), A (B, N) -> (B,) fp32.  One launch replaces
    B gram+contract pairs; padded support slots must carry zero alphas
    (the sorted-id masking contract — substrate.py zeroes them).

    The grid is (B, N / 128): each row is its own grid cell and the
    tile is fixed, so a row's floats are identical whether it runs
    alone (``predict_one``) or inside a serving bucket
    (``predict_batch``): the row-bit-exactness contract extends to the
    fused path.
    """
    TRACE_COUNTS["sv_predict"] += 1
    return _sv_predict_call(X, SV, A, **_kernel_kw(spec),
                            interpret=_interpret())


def fused_primal_step(X, Yl, w, b, *, W=None, bias=None, scale=1.0,
                      loss="hinge", eta=0.5, lam=0.01):
    """One fused online round for B stacked primal learners.

    (X (B, d), labels (B,), w (B, D), b (B,)) -> (w_new, b_new, ell,
    yhat).  With ``W``/``bias``/``scale`` set, the RFF feature map runs
    inside the kernel (featurize + predict + loss/grad + NORMA update,
    one launch); without them z = x and it is the linear family's
    round.
    """
    featurize = W is not None
    TRACE_COUNTS["rff_step" if featurize else "linear_step"] += 1
    return _primal_step_call(X, Yl, w, b, W, bias, scale=scale, loss=loss,
                             eta=eta, lam=lam, featurize=featurize,
                             interpret=_interpret())
