"""RKHS models in support-vector expansion, with Prop. 2 averaging.

The paper generalizes the synchronization protocols from Euclidean
weight vectors to a reproducing kernel Hilbert space H where models are
represented by their dual (support vector) expansion

    f(.) = sum_{x in S} alpha_x k(x, .)

JAX/XLA require static shapes, so an expansion is stored with a fixed
**budget** of slots; inactive slots carry ``alpha = 0`` and ``sv_id =
-1``.  This matches the paper's own conclusion that streaming kernel
learners must bound their model size (truncation / projection — see
compression.py), and makes the budget a first-class config knob tau.

Every support vector carries a globally unique integer id (assigned by
the learner at insertion time).  Ids make the *union* of support sets
(Prop. 2) well defined under the fixed-budget representation and drive
the byte-exact communication accounting of Sec. 3 (a vector already
known to the coordinator is never re-transmitted).  Ids are int32
everywhere — the expansions here, the sorted-id set algebra below, and
``accounting.DeviceLedger`` — and the minting scheme in core/learners
bounds runs to ``learners.MAX_INSERTIONS_PER_LEARNER`` insertions per
learner so an id can never wrap negative (which would silently read as
an empty slot).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Kernel functions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """k : X x X -> R.  ``kind`` in {gaussian, linear, poly}."""

    kind: str = "gaussian"
    gamma: float = 1.0          # gaussian: exp(-gamma ||x-y||^2)
    degree: int = 3             # poly: (x.y + coef0)^degree
    coef0: float = 1.0

    def __post_init__(self):
        if self.kind not in ("gaussian", "linear", "poly"):
            raise ValueError(f"unknown kernel {self.kind!r}")


def _matmul(A: Array, B: Array) -> Array:
    """A @ B with float32 products on every backend: the TPU's DEFAULT
    precision would round float32 operands to bfloat16."""
    # reprolint: allow[DET01] the bulk-Gram oracle's product; the bitwise path is _gram_rows
    return jnp.matmul(A, B, precision=jax.lax.Precision.HIGHEST)


def gram(spec: KernelSpec, X: Array, Y: Array) -> Array:
    """Dense Gram matrix K[i, j] = k(X[i], Y[j]).  Pure-jnp reference.

    The Pallas-accelerated path lives in repro.kernels.ops.gram; this
    function is the semantic definition used by tests as the oracle and
    by small CPU simulations directly.
    """
    X = X.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    if spec.kind == "linear":
        return _matmul(X, Y.T)  # reprolint: allow[DET01] bulk-Gram oracle; the bitwise path is _gram_rows
    if spec.kind == "poly":
        return (_matmul(X, Y.T) + spec.coef0) ** spec.degree  # reprolint: allow[DET01] bulk-Gram oracle
    # gaussian
    xx = jnp.sum(X * X, axis=-1)[:, None]
    yy = jnp.sum(Y * Y, axis=-1)[None, :]
    sq = jnp.maximum(xx + yy - 2.0 * _matmul(X, Y.T), 0.0)  # reprolint: allow[DET01] bulk-Gram oracle
    return jnp.exp(-spec.gamma * sq)


def kernel_diag(spec: KernelSpec, X: Array) -> Array:
    """k(x, x) for each row (cheap; avoids materializing the diagonal)."""
    if spec.kind == "linear":
        return jnp.sum(X * X, axis=-1)
    if spec.kind == "poly":
        return (jnp.sum(X * X, axis=-1) + spec.coef0) ** spec.degree
    return jnp.ones(X.shape[0], jnp.float32)


# ---------------------------------------------------------------------------
# Support-vector expansion with a fixed budget
# ---------------------------------------------------------------------------


class SVModel(NamedTuple):
    """A budgeted support-vector expansion.

    sv:     (budget, d)  support vector inputs (zeros when inactive)
    alpha:  (budget,)    coefficients (0 when inactive)
    sv_id:  (budget,)    unique int32 id, -1 when the slot is empty
    """

    sv: Array
    alpha: Array
    sv_id: Array

    @property
    def budget(self) -> int:
        return self.sv.shape[0]

    @property
    def dim(self) -> int:
        return self.sv.shape[1]


def empty_model(budget: int, dim: int, dtype=jnp.float32) -> SVModel:
    return SVModel(
        sv=jnp.zeros((budget, dim), dtype),
        alpha=jnp.zeros((budget,), dtype),
        sv_id=-jnp.ones((budget,), jnp.int32),
    )


def active_mask(f: SVModel) -> Array:
    return f.sv_id >= 0


def rowsum(v: Array) -> Array:
    """Sum over the last axis in one fixed pairwise order.

    The axis is zero-padded to a power of two and halved by elementwise
    adds, so no reduce op is emitted and a row's float result cannot
    depend on the other axes.  An XLA reduce picks its accumulation
    order from the whole operand shape: on the CPU (jax 0.9) a
    last-axis reduce of 5-8 elements rounds differently when the
    operand holds one or two rows than when it holds four or more,
    which is what a mesh shard of m/n learners does to an (m, d) sum
    (DESIGN.md Sec. 9).  Sums over a model's feature axis — the raw
    inputs' d, or an RFF model's D features — go through here; the SV
    budget-axis sums stay reduces (budgets are >= 9 wide, where no
    such dependence was found).
    """
    n = v.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width != n:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, width - n)])
    while width > 1:
        width //= 2
        v = v[..., :width] + v[..., width:]
    return v[..., 0]


def num_active(f: SVModel) -> Array:
    return jnp.sum(active_mask(f).astype(jnp.int32))


def _gram_rows(spec: KernelSpec, X: Array, Y: Array) -> Array:
    """``gram`` with the cross term as an explicit multiply + fixed-order
    feature-axis sum (:func:`rowsum`) instead of ``X @ Y.T``.  Same
    formula (gaussian still uses
    xx + yy - 2<x,y>), but a row's floats no longer depend on how many
    rows share the call: XLA's gemm/gemv kernels pick row-count-
    dependent accumulation orders, and the prediction path must be
    bit-identical between the single-device engine (m learners in one
    vmap) and the mesh-sharded engine (m/n per device) — DESIGN.md
    Sec. 9.  The (n, budget, d) intermediate is fine at prediction
    shapes (n is 1 in every driver); bulk Gram algebra keeps ``gram``.
    """
    X = X.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    cross = rowsum(X[:, None, :] * Y[None, :, :])
    if spec.kind == "linear":
        return cross
    if spec.kind == "poly":
        return (cross + spec.coef0) ** spec.degree
    xx = rowsum(X * X)[:, None]
    yy = rowsum(Y * Y)[None, :]
    sq = jnp.maximum(xx + yy - 2.0 * cross, 0.0)
    return jnp.exp(-spec.gamma * sq)


def predict(spec: KernelSpec, f: SVModel, X: Array) -> Array:
    """f(X) = K(X, S) alpha, masking inactive slots.

    Evaluated shape-independently (``_gram_rows`` + multiply-reduce):
    this is the value every driver's losses and service errors are
    measured from, so it must not change with the learner-axis layout.
    """
    a = jnp.where(active_mask(f), f.alpha, 0.0)
    return jnp.sum(_gram_rows(spec, X, f.sv) * a[None, :], axis=-1)


def quadform(K: Array, a: Array, b: Array) -> Array:
    """a^T K b with a layout-independent reduction order.

    Row-wise multiply + last-axis sum, then one outer sum — the same
    accumulation order whether the caller is batched, vmapped or
    sharded.  ``a @ K @ b`` would lower to gemv pairs whose reduction
    order depends on operand layout (DESIGN.md Sec. 9); every quadform
    feeding divergence / epsilon / norm values must come through here.
    """
    return jnp.sum(a * jnp.sum(K * b[None, :], axis=-1))


def norm_sq(spec: KernelSpec, f: SVModel) -> Array:
    """||f||_H^2 = alpha^T K(S, S) alpha."""
    a = jnp.where(active_mask(f), f.alpha, 0.0)
    return quadform(gram(spec, f.sv, f.sv), a, a)


def dist_sq(spec: KernelSpec, f: SVModel, g: SVModel) -> Array:
    """||f - g||_H^2 = <f,f> + <g,g> - 2<f,g>  (paper, Sec. 2)."""
    af = jnp.where(active_mask(f), f.alpha, 0.0)
    ag = jnp.where(active_mask(g), g.alpha, 0.0)
    return (
        quadform(gram(spec, f.sv, f.sv), af, af)
        + quadform(gram(spec, g.sv, g.sv), ag, ag)
        - 2.0 * quadform(gram(spec, f.sv, g.sv), af, ag)
    )


# ---------------------------------------------------------------------------
# Prop. 2: averaging a model configuration
# ---------------------------------------------------------------------------


def average_stacked(stacked: SVModel) -> SVModel:
    """Average of a stacked configuration (leading axis m) — Prop. 2.

    The average is the expansion over the union of support sets
    Sbar = U_i S^i with coefficients alphabar_s = 1/m sum_i alphabar_s^i
    (zero-padded).  Under the budgeted representation the union is the
    concatenation of all slots with coefficients divided by m; slots
    that share an sv_id are *semantically* merged (they represent the
    same point mass in H, and downstream Gram algebra treats duplicated
    rows exactly as a merged coefficient would).  The result has budget
    m * tau.
    """
    m, tau, d = stacked.sv.shape
    return SVModel(
        sv=stacked.sv.reshape(m * tau, d),
        alpha=jnp.where(
            (stacked.sv_id >= 0), stacked.alpha / m, 0.0
        ).reshape(m * tau),
        sv_id=stacked.sv_id.reshape(m * tau),
    )


# Fixed-shape set algebra over sv_id arrays: a set of ids is represented
# as a sorted int32 array whose inactive tail is padded with ID_SENTINEL.
# This is what lets the byte accounting of Sec. 3 run under jit
# (DESIGN.md Sec. 7): sorted arrays make distinctness a neighbour
# comparison and membership a searchsorted probe, both static-shape.
ID_SENTINEL = jnp.iinfo(jnp.int32).max


def sorted_unique(ids: Array) -> Tuple[Array, Array]:
    """Sorted-distinct representation of an active id set.

    ``ids`` is any int32 array where a slot is *active* iff
    ``0 <= id < ID_SENTINEL`` (empty slots are -1, sentinel padding is
    ID_SENTINEL — so the output of this function is a valid input,
    making it composable for unions).  Returns ``(uniq, count)``:
    ``uniq`` has the same (flattened) length with the distinct active
    ids sorted ascending followed by ID_SENTINEL padding, and ``count``
    is the number of distinct active ids.
    """
    flat = ids.reshape(-1)
    active = (flat >= 0) & (flat < ID_SENTINEL)
    s = jnp.sort(jnp.where(active, flat, ID_SENTINEL))
    first = jnp.concatenate(
        [s[:1] < ID_SENTINEL,
         (s[1:] != s[:-1]) & (s[1:] < ID_SENTINEL)]
    )
    uniq = jnp.sort(jnp.where(first, s, ID_SENTINEL))
    return uniq, jnp.sum(first.astype(jnp.int32))


def count_members(queries: Array, sorted_ids: Array) -> Array:
    """|Q ∩ A| for a sorted-unique query array Q and sorted id array A.

    Both arrays use the ID_SENTINEL padding convention of
    ``sorted_unique``; sentinel slots never count as members.
    """
    idx = jnp.clip(jnp.searchsorted(sorted_ids, queries), 0,
                   sorted_ids.shape[0] - 1)
    hit = (sorted_ids[idx] == queries) & (queries < ID_SENTINEL)
    return jnp.sum(hit.astype(jnp.int32))


def union_unique_count(stacked_or_avg_sv_id: Array) -> Array:
    """|Sbar| — the number of *distinct* active support vector ids.

    Used by the communication accounting: duplicated ids (support
    vectors shared among learners after an earlier synchronization) are
    transmitted / stored once.
    """
    return sorted_unique(stacked_or_avg_sv_id)[1]


def stacked_dist_to(spec: KernelSpec, stacked: SVModel, ref: SVModel) -> Array:
    """Per-learner ||f_i - r||^2, shape (m,).  Local-condition values."""

    def one(f: SVModel) -> Array:
        return dist_sq(spec, f, ref)

    return jax.vmap(one)(stacked)


def divergence_stacked(spec: KernelSpec, stacked: SVModel) -> Array:
    """delta(f) = 1/m sum_i ||f_i - fbar||^2 over RKHS models (Eq. 1)."""
    fbar = average_stacked(stacked)
    return jnp.mean(stacked_dist_to(spec, stacked, fbar))


# ---------------------------------------------------------------------------
# Slot insertion (shared by the online learners)
# ---------------------------------------------------------------------------


def insert_sv(
    f: SVModel,
    x: Array,
    alpha_new: Array,
    new_id: Array,
    evict: str = "smallest",
) -> SVModel:
    """Insert a support vector into a budgeted expansion.

    If a free slot exists it is used; otherwise the slot chosen by the
    eviction policy is overwritten (``smallest`` |alpha| — the
    truncation rule of Kivinen et al. [12]; ``oldest`` — FIFO).  The
    eviction IS the paper's model-compression step: dropping a slot
    perturbs the exact loss-proportional update by at most
    epsilon = |alpha_evicted| * sqrt(k(x_e, x_e)), which is what makes
    the update *approximately* loss-proportional (Lemma 3).
    """
    act = active_mask(f)
    # score: free slots first (score -inf), then per-policy.
    if evict == "smallest":
        score = jnp.where(act, jnp.abs(f.alpha), -jnp.inf)
    elif evict == "oldest":
        score = jnp.where(act, f.sv_id.astype(jnp.float32), -jnp.inf)
    else:
        raise ValueError(f"unknown eviction policy {evict!r}")
    slot = jnp.argmin(score)
    return SVModel(
        sv=f.sv.at[slot].set(x.astype(f.sv.dtype)),
        alpha=f.alpha.at[slot].set(alpha_new.astype(f.alpha.dtype)),
        sv_id=f.sv_id.at[slot].set(new_id.astype(jnp.int32)),
    )


def scale_model(f: SVModel, c: Array) -> SVModel:
    """c * f  (coefficient scaling — e.g. the (1 - eta*lambda) decay)."""
    return f._replace(alpha=f.alpha * c)


def pad_to_budget(f: SVModel, tau: int) -> SVModel:
    """Pad (inactive fill) or truncate an expansion to budget tau.

    Both drivers use this when learners adopt a synchronized model, so
    the serial and async adopt paths stay bit-identical.
    """

    def pad(v, fill):
        if v.shape[0] < tau:
            width = [(0, tau - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
            v = jnp.pad(v, width, constant_values=fill)
        return v[:tau]

    return SVModel(sv=pad(f.sv, 0.0), alpha=pad(f.alpha, 0.0),
                   sv_id=pad(f.sv_id, -1))
