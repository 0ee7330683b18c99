"""Public wrappers around the Pallas kernels.

Responsibilities:
- pad inputs to MXU-aligned block multiples (zero padding is exact for
  the feature dim of every kernel kind; padded rows/cols are cropped
  from outputs, and padded alpha/beta entries are zero so quadform is
  exact);
- choose interpret mode off-TPU: on a CPU backend interpret=True
  executes the kernel bodies in Python, which is how the test suite
  checks the TPU kernels' numerics; on a TPU backend they compile to
  Mosaic (tests/test_tpu_compile.py compiles them for a described v5e);
- resolve block sizes through kernels/autotune.py (the seed-era
  hardcoded 128s are now the *defaults* the tuner falls back to; pass
  explicit ``block_*`` ints to bypass it);
- fall back to the pure-jnp reference for tiny shapes where a Pallas
  launch is not worth it (``engages`` is the one shared threshold).

Structure: each public op is an *eager* resolver (fallback branch,
tuned-block lookup, launch counting) around a module-level jitted
launcher whose static arguments are exactly the kernel-shape-relevant
knobs.  Calling an op eagerly pays one dict lookup + one jit-cache hit
per call; calling it inside an outer jit (the substrate under the scan
engine) resolves everything at trace time and inlines the launcher.

``LAUNCH_COUNTS`` ticks once per *traced* Pallas launch (per call when
eager) — the path-proof used by the backend-parity tests and the
serving ``bucket_predict_hits_pallas`` claim: parity says the numbers
match, the counter says the fused kernel actually produced them.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import autotune, ref
from .fused import primal_step_pallas, sv_predict_pallas
from .gram import gram_pallas
from .quadform import quadform_pallas
from .rff import rff_pallas

_LANE = 128          # TPU lane width: last-dim alignment
_MIN_PALLAS = 128    # below this, use the jnp reference

LAUNCH_COUNTS: collections.Counter = collections.Counter()

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

    The single fallback threshold every op shares: a launch engages
    when any blocked extent reaches ``_MIN_PALLAS``.  The substrate
    layer keys its own backend dispatch on this, so "pallas backend,
    tiny model" runs the reference expressions bit-for-bit.
    """
    return max(int(d) for d in dims) >= _MIN_PALLAS


def reset_launch_counts() -> None:
    LAUNCH_COUNTS.clear()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


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
    static_argnames=("kind", "gamma", "degree", "coef0", "block_m",
                     "block_n", "interpret"),
)
def _gram_call(X, Y, *, kind, gamma, degree, coef0, block_m, block_n,
               interpret):
    M, N = X.shape[0], Y.shape[0]
    Xp = _pad_to(_pad_to(X, 0, block_m), 1, _LANE)
    Yp = _pad_to(_pad_to(Y, 0, block_n), 1, _LANE)
    K = gram_pallas(
        Xp, Yp, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return K[:M, :N]


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "block_m", "block_d", "interpret"),
)
def _rff_call(X, W, b, *, num_features, block_m, block_d, interpret):
    M, D = X.shape[0], W.shape[0]
    Xp = _pad_to(_pad_to(X, 0, block_m), 1, _LANE)
    Wp = _pad_to(_pad_to(W, 0, block_d), 1, _LANE)
    bp = _pad_to(b, 0, block_d)
    Z = rff_pallas(
        Xp, Wp, bp, num_features=num_features, block_m=block_m,
        block_d=block_d, interpret=interpret,
    )
    return Z[:M, :D]


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "block_m",
                     "block_n", "interpret"),
)
def _quadform_call(X, Y, alpha, beta, *, kind, gamma, degree, coef0,
                   block_m, block_n, interpret):
    Xp = _pad_to(_pad_to(X, 0, block_m), 1, _LANE)
    Yp = _pad_to(_pad_to(Y, 0, block_n), 1, _LANE)
    ap = _pad_to(alpha, 0, block_m)
    bp = _pad_to(beta, 0, block_n)
    return quadform_pallas(
        Xp, Yp, ap, bp, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("kind", "gamma", "degree", "coef0", "block_n",
                     "interpret"),
)
def _sv_predict_call(X, SV, A, *, kind, gamma, degree, coef0, block_n,
                     interpret):
    Xp = _pad_to(X, 1, _LANE)
    SVp = _pad_to(_pad_to(SV, 1, block_n), 2, _LANE)
    Ap = _pad_to(A, 1, block_n)
    return sv_predict_pallas(
        Xp, SVp, Ap, kind=kind, gamma=gamma, degree=degree, coef0=coef0,
        block_n=block_n, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "loss", "eta", "lam", "block_m",
                     "featurize", "interpret"),
)
def _primal_step_call(X, Yl, w, b, W, bias, *, scale, loss, eta, lam,
                      block_m, featurize, interpret):
    B, D = w.shape
    Xp = _pad_to(_pad_to(X, 0, block_m), 1, _LANE)
    wp = _pad_to(_pad_to(w, 0, block_m), 1, _LANE)
    yp = _pad_to(Yl, 0, block_m)
    bp = _pad_to(b, 0, block_m)
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
        eta=eta, lam=lam, block_m=block_m, interpret=interpret,
    )
    return w_new[:B, :D], b_new[:B], ell[:B], yhat[:B]


# ---------------------------------------------------------------------------
# Public ops
# ---------------------------------------------------------------------------


def _tuned(op, dims, dtype, key_kind, launcher, args, statics):
    """Tiles for one launch of ``launcher(*args, **statics(blocks))``,
    through the autotuner: a candidate is compiled from these exact
    operands, so the search times the launch it will make."""
    def compile_candidate(blocks):
        exe = launcher.lower(*args, **statics(blocks)).compile()
        return lambda: exe(*args)

    return autotune.tuned_blocks(op, dims, dtype=str(dtype), kind=key_kind,
                                 compile_candidate=compile_candidate,
                                 operands=args)


def gram(X, Y, *, kind="gaussian", gamma=1.0, degree=3, coef0=1.0,
         block_m=None, block_n=None, force_pallas=False):
    """K(X, Y): (M, d), (N, d) -> (M, N) fp32."""
    M, N = X.shape[0], Y.shape[0]
    if not force_pallas and not engages(M, N):
        return ref.gram_ref(X, Y, kind=kind, gamma=gamma, degree=degree,
                            coef0=coef0)

    def statics(blocks):
        return dict(kind=kind, gamma=gamma, degree=degree, coef0=coef0,
                    block_m=blocks[0], block_n=blocks[1],
                    interpret=_interpret())

    if block_m is None or block_n is None:
        block_m, block_n = _tuned("gram", (M, N), X.dtype,
                                  f"{kind}:d={X.shape[1]}", _gram_call,
                                  (X, Y), statics)
    LAUNCH_COUNTS["gram"] += 1
    return _gram_call(X, Y, **statics((block_m, block_n)))


def rff_features(X, W, b, *, num_features=None, block_m=None, block_d=None,
                 force_pallas=False):
    """phi(X): (M, d) with W (D, d), b (D,) -> (M, D) fp32."""
    M, D = X.shape[0], W.shape[0]
    nf = num_features or D
    if not force_pallas and not engages(M, D):
        return ref.rff_ref(X, W, b, num_features=nf)

    def statics(blocks):
        return dict(num_features=nf, block_m=blocks[0], block_d=blocks[1],
                    interpret=_interpret())

    if block_m is None or block_d is None:
        block_m, block_d = _tuned("rff", (M, D), X.dtype,
                                  f"d={X.shape[1]}", _rff_call, (X, W, b),
                                  statics)
    LAUNCH_COUNTS["rff"] += 1
    return _rff_call(X, W, b, **statics((block_m, block_d)))


def quadform(X, Y, alpha, beta, *, kind="gaussian", gamma=1.0, degree=3,
             coef0=1.0, block_m=None, block_n=None, force_pallas=False):
    """alpha^T K(X, Y) beta -> scalar fp32, without materializing K in HBM."""
    M, N = X.shape[0], Y.shape[0]
    if not force_pallas and not engages(M, N):
        return ref.quadform_ref(X, Y, alpha, beta, kind=kind, gamma=gamma,
                                degree=degree, coef0=coef0)

    def statics(blocks):
        return dict(kind=kind, gamma=gamma, degree=degree, coef0=coef0,
                    block_m=blocks[0], block_n=blocks[1],
                    interpret=_interpret())

    if block_m is None or block_n is None:
        block_m, block_n = _tuned("quadform", (M, N), X.dtype,
                                  f"{kind}:d={X.shape[1]}", _quadform_call,
                                  (X, Y, alpha, beta), statics)
    LAUNCH_COUNTS["quadform"] += 1
    return _quadform_call(X, Y, alpha, beta, **statics((block_m, block_n)))


def rkhs_dist_sq(X, Y, alpha, beta, *, kind="gaussian", gamma=1.0,
                 degree=3, coef0=1.0):
    """||f - g||_H^2 via three fused quadratic forms (never materializes
    any Gram matrix in HBM) — the divergence-monitoring hot path."""
    kw = dict(kind=kind, gamma=gamma, degree=degree, coef0=coef0)
    return (
        quadform(X, X, alpha, alpha, **kw)
        + quadform(Y, Y, beta, beta, **kw)
        - 2.0 * quadform(X, Y, alpha, beta, **kw)
    )


def sv_predict(X, SV, A, *, kind="gaussian", gamma=1.0, degree=3,
               coef0=1.0, block_n=None, force_pallas=False):
    """Fused batched SV predictions: yhat_i = sum_j k(X_i, SV_ij) A_ij.

    X (B, d), SV (B, N, d), A (B, N) -> (B,) fp32.  One launch replaces
    B gram+contract pairs; padded support slots must carry zero alphas
    (the sorted-id masking contract — substrate.py zeroes them).

    Engagement and the tuned block depend on the budget axis N (and d
    via the tune key) but never on B, so a row's floats — and its
    branch — are identical whether it runs alone (``predict_one``) or
    inside a serving bucket (``predict_batch``): the row-bit-exactness
    contract extends to the fused path.
    """
    B, N, d = SV.shape
    if not force_pallas and not engages(N):
        return ref.sv_predict_ref(X, SV, A, kind=kind, gamma=gamma,
                                  degree=degree, coef0=coef0)

    def statics(blocks):
        return dict(kind=kind, gamma=gamma, degree=degree, coef0=coef0,
                    block_n=blocks[0], interpret=_interpret())

    if block_n is None:
        (block_n,) = _tuned("sv_predict", (N,), SV.dtype, f"{kind}:d={d}",
                            _sv_predict_call, (X, SV, A), statics)
    LAUNCH_COUNTS["sv_predict"] += 1
    return _sv_predict_call(X, SV, A, **statics((block_n,)))


def fused_primal_step(X, Yl, w, b, *, W=None, bias=None, scale=1.0,
                      loss="hinge", eta=0.5, lam=0.01, block_m=None,
                      force_pallas=False):
    """One fused online round for B stacked primal learners.

    (X (B, d), labels (B,), w (B, D), b (B,)) -> (w_new, b_new, ell,
    yhat).  With ``W``/``bias``/``scale`` set, the RFF feature map runs
    inside the kernel (featurize + predict + loss/grad + NORMA update,
    one launch); without them z = x and it is the linear family's
    round.
    """
    B = X.shape[0]
    D = w.shape[1]
    featurize = W is not None
    op = "rff_step" if featurize else "linear_step"
    if not force_pallas and not engages(B, D):
        return ref.primal_step_ref(X, Yl, w, b, W=W, bias=bias, scale=scale,
                                   loss=loss, eta=eta, lam=lam)

    def statics(blocks):
        return dict(scale=scale, loss=loss, eta=eta, lam=lam,
                    block_m=blocks[0], featurize=featurize,
                    interpret=_interpret())

    args = (X, Yl, w, b, W, bias)
    if block_m is None:
        (block_m,) = _tuned(op, (B,), X.dtype,
                            f"d={X.shape[1]}:D={D}:{loss}",
                            _primal_step_call, args, statics)
    LAUNCH_COUNTS[op] += 1
    return _primal_step_call(*args, **statics((block_m,)))


# ---------------------------------------------------------------------------
# KernelSpec-driven entry points (the substrate layer's pallas backend)
# ---------------------------------------------------------------------------
#
# ``spec`` is duck-typed against core.rkhs.KernelSpec (kind / gamma /
# degree / coef0) so this package stays import-independent of core.
# These are what core.substrate dispatches to under backend="pallas"
# (DESIGN.md Sec. 8 and 12).


def gram_spec(spec, X, Y, **kw):
    """K(X, Y) for a core.rkhs.KernelSpec."""
    return gram(X, Y, kind=spec.kind, gamma=spec.gamma, degree=spec.degree,
                coef0=spec.coef0, **kw)


def quadform_spec(spec, X, Y, alpha, beta, **kw):
    """alpha^T K(X, Y) beta for a core.rkhs.KernelSpec."""
    return quadform(X, Y, alpha, beta, kind=spec.kind, gamma=spec.gamma,
                    degree=spec.degree, coef0=spec.coef0, **kw)


def rkhs_dist_sq_spec(spec, X, Y, alpha, beta):
    """||f - g||_H^2 for a core.rkhs.KernelSpec (three fused quadforms)."""
    return rkhs_dist_sq(X, Y, alpha, beta, kind=spec.kind, gamma=spec.gamma,
                        degree=spec.degree, coef0=spec.coef0)


def sv_predict_spec(spec, X, SV, A, **kw):
    """Fused batched SV predictions for a core.rkhs.KernelSpec."""
    return sv_predict(X, SV, A, kind=spec.kind, gamma=spec.gamma,
                      degree=spec.degree, coef0=spec.coef0, **kw)
