"""Model compression for support-vector expansions (Sec. 3/4).

Two families from the paper:

- **Truncation** (Kivinen et al. [12]): drop support vectors with small
  coefficients.  For SGD with learning rate lambda the compression
  error is bounded by epsilon in O((1/lambda)(1-lambda)^tau) for budget
  tau, which makes the compressed update approximately
  loss-proportional and the dynamic protocol *adaptive* (and with
  consistency, *efficient*).
- **Projection** (Orabona et al. [15], Wang & Vucetic [20]): project
  the dropped support vectors onto the span of the kept ones, i.e.
  solve  K_kk c = K_kd beta  and fold c into the kept coefficients.
  Strictly smaller epsilon than truncation for the same budget, at
  O(tau^3) compression cost; no formal bound on |S| in the paper.

Both return the new model *and* the exact compression error
epsilon = ||f - f~||_H, so the caller can verify Lemma 3 / Theorem 4
empirically (tests/test_bounds.py) and drive the epsilon-dependent
terms of the loss bound.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .rkhs import (ID_SENTINEL, KernelSpec, SVModel, active_mask, gram,
                   quadform)

Array = jnp.ndarray

#: The repo-wide default compression method.  Every entry point that
#: compresses a synchronized model — ``SVSubstrate.compress_method``,
#: ``substrate_of``'s LearnerConfig resolution, the legacy simulation
#: drivers — defaults to this one name, so "what does None mean"
#: resolves to a single constant instead of per-call-site comments.
DEFAULT_METHOD = "truncate"


def _top_tau_mask(f: SVModel, tau: int) -> Array:
    """Boolean mask of the tau active slots with the largest |alpha|."""
    act = active_mask(f)
    score = jnp.where(act, jnp.abs(f.alpha), -jnp.inf)
    order = jnp.argsort(-score)  # descending; inactive (-inf) sink to the end
    keep_idx = order[:tau]
    mask = jnp.zeros(f.budget, bool).at[keep_idx].set(True)
    return mask & act


def _masked_model(f: SVModel, keep: Array) -> SVModel:
    return SVModel(
        sv=jnp.where(keep[:, None], f.sv, 0.0),
        alpha=jnp.where(keep, f.alpha, 0.0),
        sv_id=jnp.where(keep, f.sv_id, -1),
    )


def _pack_to_budget(f: SVModel, keep: Array, tau: int) -> SVModel:
    """Gather the kept slots into a tau-slot model (static shapes)."""
    # indices of kept slots first (stable), padded with dropped slots
    order = jnp.argsort(~keep)  # kept (False<True inverted) first, stable
    idx = order[:tau]
    valid = keep[idx]
    return SVModel(
        sv=jnp.where(valid[:, None], f.sv[idx], 0.0),
        alpha=jnp.where(valid, f.alpha[idx], 0.0),
        sv_id=jnp.where(valid, f.sv_id[idx], -1),
    )


class MergedDrop(NamedTuple):
    """The dropped part of an expansion with its slots merged by id.

    slot:  (n,) a slot of the expansion that holds each distinct dropped
           point, ascending by id, then 0
    beta:  (n,) each point's summed dropped coefficient, 0 on padding
    count: ()   u, the number of distinct dropped points
    """

    slot: Array
    beta: Array
    count: Array


def _segment_sums(values: Array, starts: Array) -> Array:
    """Inclusive sums of ``values`` within runs that open where ``starts``
    is True: each run's total lands on its last slot.  A segmented
    associative scan, so the order of the additions depends on the
    length alone."""

    def combine(a, b):
        sa, va = a
        sb, vb = b
        return sa | sb, jnp.where(sb, vb, va + vb)

    return lax.associative_scan(combine, (starts, values))[1]


def merge_dropped(f: SVModel, beta: Array) -> MergedDrop:
    """Merge the coefficients ``beta`` (zero on every kept or empty slot)
    over the slots of f that share an sv_id.

    Slots with one id are one point (``rkhs.average_stacked``), so
    sum_s beta_s k(x_s, .) = sum_u B_u k(x_u, .) over the distinct ids u,
    with B_u the sum of beta over u's slots.  A slot is keyed by its id
    where beta != 0, else by ID_SENTINEL; one stable sort carries beta and
    the slot index with the key, the runs of equal keys are summed in
    slot order by a segmented scan, and a second sort moves one
    representative per run to the front.  The vectors stay where they
    are: a sort that carried the d vector columns as well compiles for
    a TPU about six times slower at 16K slots, and the tiles of
    ``truncate`` gather only the rows they use.  ``count`` is the number
    of distinct dropped points, the u over which ``truncate`` evaluates
    epsilon.
    """
    key = jnp.where(beta != 0, f.sv_id, ID_SENTINEL)
    slot = lax.iota(jnp.int32, f.budget)
    key, b, slot = lax.sort((key, beta, slot), num_keys=1, is_stable=True)
    change = key[1:] != key[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), change])
    last = jnp.concatenate([change, jnp.ones(1, bool)]) & (key < ID_SENTINEL)
    b = jnp.where(last, _segment_sums(b, first), 0.0)
    rep = jnp.where(last, key, ID_SENTINEL)
    _, b, slot = lax.sort((rep, b, jnp.where(last, slot, 0)), num_keys=1,
                          is_stable=True)
    return MergedDrop(slot=slot, beta=b,
                      count=jnp.sum(last.astype(jnp.int32)))


def _dropped_beta(f: SVModel, keep: Array) -> Array:
    return jnp.where(active_mask(f) & ~keep, f.alpha, 0.0)


def distinct_dropped(f: SVModel, tau: int) -> Array:
    """u: how many distinct points ``truncate(spec, f, tau)`` drops — the
    side of the Gram its epsilon is evaluated over, against f.budget for
    the slot-by-slot one."""
    return merge_dropped(f, _dropped_beta(f, _top_tau_mask(f, tau))).count


#: Side of the tiles of the compression error's Gram.
_TILE = 512
#: Tiles a side beyond which one (n, n) Gram over the merged points is
#: cheaper than the serial tile loop: 24 a side (u <= 12288) at about
#: 4.7 us a tile against 2.9 ms for the (16384, 16384) Gram, on a TPU
#: v5e (PERF.md section 5).
_MAX_TILES = 24


def _merged_norm_sq(spec: KernelSpec, sv: Array, drop: MergedDrop) -> Array:
    """B^T K B over the first ``drop.count`` merged points (rows of ``sv``
    picked by ``drop.slot``).  Up to _MAX_TILES tiles a side, in
    (tb, tb) tiles with tb = min(_TILE, n); beyond that, where few
    learners share points, as one (n, n) tile."""
    n = drop.beta.shape[0]
    tb = min(_TILE, n)
    if tb == n:
        return _tiled_norm_sq(spec, sv, drop, n)
    return lax.cond(drop.count <= _MAX_TILES * tb,
                    lambda: _tiled_norm_sq(spec, sv, drop, tb),
                    lambda: _tiled_norm_sq(spec, sv, drop, n))


def _tiled_norm_sq(spec: KernelSpec, sv: Array, drop: MergedDrop,
                   tb: int) -> Array:
    """B^T K B in (tb, tb) tiles, ceil(count / tb) a side, summed
    row-major.  Padding rows carry B = 0, so a partial tile adds nothing;
    count = 0 runs no tile and gives exactly 0."""
    n = drop.beta.shape[0]
    pad = -n % tb
    slot = jnp.pad(drop.slot, (0, pad))
    b = jnp.pad(drop.beta, (0, pad))
    tiles = (drop.count + tb - 1) // tb

    def tile(i):
        return (sv[lax.dynamic_slice_in_dim(slot, i * tb, tb)],
                lax.dynamic_slice_in_dim(b, i * tb, tb))

    def row(i, acc):
        xi, bi = tile(i)

        def col(j, acc):
            xj, bj = tile(j)
            return acc + quadform(gram(spec, xi, xj), bi, bj)

        return lax.fori_loop(0, tiles, col, acc)

    return lax.fori_loop(0, tiles, row, jnp.zeros((), jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 2))
def truncate(
    spec: KernelSpec, f: SVModel, tau: int
) -> Tuple[SVModel, Array]:
    """Truncate f to at most tau support vectors (smallest-|alpha| rule).

    Returns (f_trunc with budget tau, epsilon) where
    epsilon^2 = beta^T K_dd beta over the dropped part — the exact RKHS
    norm of the removed component.  It is evaluated over the distinct
    dropped ids (``merge_dropped``): after a sync every learner holds
    the same points, so the averaged slots repeat each id up to m times,
    and the Gram shrinks from f.budget^2 to u^2 kernel values (up to
    ``_MAX_TILES`` tiles a side; beyond, one f.budget^2 Gram over the
    merged points).
    Jitted, so that eager callers (the loop driver, the async runtime)
    run the sorts and the tile loop as one program.
    """
    keep = _top_tau_mask(f, tau)
    drop = merge_dropped(f, _dropped_beta(f, keep))
    eps_sq = _merged_norm_sq(spec, f.sv, drop)
    return _pack_to_budget(f, keep, tau), jnp.sqrt(jnp.maximum(eps_sq, 0.0))


def project(
    spec: KernelSpec, f: SVModel, tau: int, ridge: float = 1e-6
) -> Tuple[SVModel, Array]:
    """Compress f to tau SVs by projecting dropped SVs on the kept span.

    Solves (K_kk + ridge I) c = K_kd beta and adds c to the kept
    coefficients.  epsilon^2 = beta^T K_dd beta - beta^T K_dk c  (the
    residual of the orthogonal projection; clipped at 0 for numerical
    safety).
    """
    keep = _top_tau_mask(f, tau)
    beta = _dropped_beta(f, keep)

    K = gram(spec, f.sv, f.sv)
    keep_f = keep.astype(K.dtype)
    # Restrict to kept rows/cols by masking; ridge keeps the masked-out
    # diagonal invertible without affecting the kept block's solution.
    K_kk = K * keep_f[:, None] * keep_f[None, :]
    K_kk = K_kk + (ridge + (1.0 - keep_f))[:, None] * jnp.eye(f.budget,
                                                              dtype=K.dtype)
    rhs = jnp.sum(K * beta[None, :], axis=-1) * keep_f
    c = jnp.linalg.solve(K_kk, rhs)
    c = c * keep_f

    eps_sq = quadform(K, beta, beta) - quadform(K, beta, c)
    eps_sq = jnp.maximum(eps_sq, 0.0)

    merged = f._replace(alpha=jnp.where(keep, f.alpha + c, f.alpha))
    return _pack_to_budget(merged, keep, tau), jnp.sqrt(eps_sq)


def compress(
    spec: KernelSpec, f: SVModel, tau: int, method: str = DEFAULT_METHOD
) -> Tuple[SVModel, Array]:
    if method == "truncate":
        return truncate(spec, f, tau)
    if method == "project":
        return project(spec, f, tau)
    raise ValueError(f"unknown compression method {method!r}")


def truncation_error_bound(lam: float, tau: int) -> float:
    """The [12] bound:  epsilon in O((1/lam) (1-lam)^tau)  for SGD with
    learning rate lam and budget tau.  Used by tests to check the
    measured epsilon stays within a constant of the bound."""
    return (1.0 / lam) * (1.0 - lam) ** tau
