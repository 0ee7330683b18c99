"""Compression tests: exact epsilon, projection <= truncation error,
and the Kivinen et al. truncation bound shape (Sec. 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, rkhs
from repro.core.rkhs import KernelSpec, SVModel


def _model(budget, d, n_active, seed):
    rng = np.random.default_rng(seed)
    sv = np.zeros((budget, d), np.float32)
    alpha = np.zeros((budget,), np.float32)
    ids = -np.ones((budget,), np.int32)
    sv[:n_active] = rng.normal(size=(n_active, d))
    alpha[:n_active] = rng.normal(size=(n_active,)) * 0.5
    ids[:n_active] = np.arange(n_active)
    return SVModel(sv=jnp.asarray(sv), alpha=jnp.asarray(alpha),
                   sv_id=jnp.asarray(ids))


@pytest.mark.parametrize("method", ["truncate", "project"])
def test_epsilon_is_exact_rkhs_distance(method):
    """epsilon returned by compress equals ||f - f~||_H computed
    independently (compressed model compared against the original)."""
    spec = KernelSpec(kind="gaussian", gamma=0.5)
    f = _model(10, 3, 10, seed=0)
    fc, eps = compression.compress(spec, f, tau=6, method=method)
    assert fc.budget == 6
    d2 = float(rkhs.dist_sq(spec, f, fc))
    np.testing.assert_allclose(float(eps) ** 2, max(d2, 0.0), rtol=1e-3,
                               atol=1e-4)


def test_projection_never_worse_than_truncation():
    spec = KernelSpec(kind="gaussian", gamma=0.5)
    for seed in range(5):
        f = _model(12, 4, 12, seed=seed)
        _, e_t = compression.truncate(spec, f, tau=7)
        _, e_p = compression.project(spec, f, tau=7)
        assert float(e_p) <= float(e_t) + 1e-4


def test_truncation_keeps_largest_coefficients():
    spec = KernelSpec(kind="linear")
    f = _model(8, 3, 8, seed=1)
    fc, _ = compression.truncate(spec, f, tau=4)
    kept = set(np.asarray(fc.sv_id)[np.asarray(fc.sv_id) >= 0].tolist())
    order = np.argsort(-np.abs(np.asarray(f.alpha)))[:4]
    want = set(np.asarray(f.sv_id)[order].tolist())
    assert kept == want


def test_compress_noop_when_under_budget():
    spec = KernelSpec(kind="gaussian")
    f = _model(8, 3, 4, seed=2)
    fc, eps = compression.truncate(spec, f, tau=6)
    assert float(eps) < 1e-6
    assert int(rkhs.num_active(fc)) == 4


def test_truncation_error_bound_decreases_in_tau():
    b = [compression.truncation_error_bound(0.1, t) for t in (5, 10, 20, 40)]
    assert all(x > y for x, y in zip(b, b[1:]))


def test_compressed_update_is_approximately_loss_proportional():
    """Lemma 3 precondition: ||phi~(f) - phi(f)|| <= eps, where phi~ is
    the update followed by compression.  We verify the measured eps of
    the compression step bounds the function-space deviation."""
    spec = KernelSpec(kind="gaussian", gamma=0.5)
    f = _model(12, 3, 12, seed=3)
    fc, eps = compression.truncate(spec, f, tau=8)
    # deviation in prediction at arbitrary points is bounded by
    # |f(x) - fc(x)| <= ||f - fc|| * sqrt(k(x,x)) = eps * 1 (gaussian)
    X = np.random.default_rng(4).normal(size=(50, 3)).astype(np.float32)
    gap = np.abs(np.asarray(rkhs.predict(spec, f, jnp.asarray(X)))
                 - np.asarray(rkhs.predict(spec, fc, jnp.asarray(X))))
    assert float(gap.max()) <= float(eps) + 1e-4


# -- epsilon over the distinct dropped ids ----------------------------------

GAUSS = KernelSpec(kind="gaussian", gamma=0.3)


def _slots(sv, alpha, ids):
    return SVModel(sv=jnp.asarray(np.asarray(sv, np.float32)),
                   alpha=jnp.asarray(np.asarray(alpha, np.float32)),
                   sv_id=jnp.asarray(np.asarray(ids, np.int32)))


def _shared_plus_new(m=8, tau=64, shared=40, new=20, seed=0):
    """The union of m learners after a sync: each holds the same
    ``shared`` points (ids 0..shared-1) plus ``new`` points of its own,
    averaged slot by slot as rkhs.average_stacked does."""
    rng = np.random.default_rng(seed)
    d = 8
    sv = np.zeros((m, tau, d), np.float32)
    alpha = np.zeros((m, tau), np.float32)
    ids = -np.ones((m, tau), np.int32)
    xs, a = rng.normal(size=(shared, d)), rng.normal(size=shared)
    for i in range(m):
        sv[i, :shared], alpha[i, :shared] = xs, a
        ids[i, :shared] = np.arange(shared)
        sv[i, shared:shared + new] = rng.normal(size=(new, d))
        alpha[i, shared:shared + new] = 0.3 * rng.normal(size=new)
        ids[i, shared:shared + new] = 1000 + new * i + np.arange(new)
    return rkhs.average_stacked(_slots(sv, alpha, ids)), tau


def _distinct(n, tau, seed, d=8, holes=()):
    rng = np.random.default_rng(seed)
    ids = np.arange(n)
    alpha = rng.normal(size=n)
    ids[list(holes)] = -1
    alpha[list(holes)] = 0.0
    return _slots(rng.normal(size=(n, d)), alpha, ids), tau


CASES = {
    # duplicated ids: u far below the slot count
    "shared_plus_new": lambda: _shared_plus_new(),
    # every id distinct: u = 1100 rows in (512, 512) tiles, 3 a side,
    # the last partial
    "all_distinct_tiles": lambda: _distinct(1200, 100, seed=1),
    # one tile (n < 512), u = 293 not a multiple of the tile
    "one_partial_tile": lambda: _distinct(300, 7, seed=2),
    # empty (-1) slots among the dropped ones
    "empty_slots": lambda: _distinct(96, 20, seed=3,
                                     holes=(0, 5, 17, 40, 41, 95)),
    # at most tau active slots: nothing dropped
    "nothing_dropped": lambda: _distinct(64, 64, seed=4, holes=range(10)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_merged_epsilon_matches_dense_slot_formula(case):
    """truncate's epsilon, evaluated over the distinct dropped ids,
    equals the dense slot-by-slot beta^T K beta; its model is the
    parent's keep-and-pack output bitwise; two calls agree bitwise."""
    f, tau = CASES[case]()
    fc, eps = jax.jit(compression.truncate, static_argnums=(0, 2))(
        GAUSS, f, tau)
    keep = compression._top_tau_mask(f, tau)
    beta = jnp.where(rkhs.active_mask(f) & ~keep, f.alpha, 0.0)
    dense = float(jnp.sqrt(rkhs.quadform(rkhs.gram(GAUSS, f.sv, f.sv),
                                         beta, beta)))
    if case == "nothing_dropped":
        assert float(eps) == 0.0
    else:
        assert dense > 0.0
        np.testing.assert_allclose(float(eps), dense, rtol=1e-5)
    packed = compression._pack_to_budget(f, keep, tau)
    for got, want in zip(fc, packed):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _, again = compression.truncate(GAUSS, f, tau)
    _, again_jit = jax.jit(compression.truncate, static_argnums=(0, 2))(
        GAUSS, f, tau)
    assert np.asarray(again_jit).tobytes() == np.asarray(eps).tobytes()
    np.testing.assert_allclose(float(again), float(eps), rtol=1e-6)


@pytest.mark.parametrize("max_tiles", [2, 3])
def test_merged_epsilon_picks_tile_loop_or_one_gram_by_u(monkeypatch,
                                                         max_tiles):
    """u = 1100 merged points need 3 tiles a side: allowed 3, the tile
    loop runs; allowed 2, one (n, n) Gram over the merged points.  Both
    give the dense slot formula."""
    f, tau = CASES["all_distinct_tiles"]()
    keep = compression._top_tau_mask(f, tau)
    beta = compression._dropped_beta(f, keep)
    drop = compression.merge_dropped(f, beta)
    monkeypatch.setattr(compression, "_MAX_TILES", max_tiles)
    got = compression._merged_norm_sq(GAUSS, f.sv, drop)
    dense = rkhs.quadform(rkhs.gram(GAUSS, f.sv, f.sv), beta, beta)
    np.testing.assert_allclose(float(got), float(dense), rtol=1e-5)


@pytest.mark.parametrize("case,want", [
    # 8 learners x (40 shared + 20 own) points, 64 kept: the 64 largest
    # |alpha| slots hold 8 copies of each of the 8 largest shared
    # points, so 32 shared and all 160 own points are dropped
    ("shared_plus_new", 192),
    ("all_distinct_tiles", 1100),
    ("empty_slots", 70),
    ("nothing_dropped", 0),
])
def test_distinct_dropped_counts_merged_points(case, want):
    f, tau = CASES[case]()
    assert int(compression.distinct_dropped(f, tau)) == want
