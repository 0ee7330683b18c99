"""Backend-parity matrix: backend="pallas" vs backend="reference".

The contract (ISSUE 7, DESIGN.md Sec. 12) has two regimes:

- BELOW the Pallas launch threshold (kernels.ops.engages is False) the
  pallas backend runs the reference expressions verbatim, so every
  observable is BIT-IDENTICAL — asserted with exact equality here, and
  it is what makes the Def. 1 byte ledger backend-independent by
  construction (tools/substrate_matrix.py runs the full protocol
  matrix on it).
- AT OR ABOVE the threshold the fused kernels produce the numbers,
  compared against the reference within the ONE pinned tolerance in
  conftest.py (assert_backend_parity).

The deterministic sweep below runs everywhere; the hypothesis sweep at
the bottom widens the same assertions over random shapes when
hypothesis is installed (CI always has it — pyproject pins nothing
locally, so it import-skips, mirroring tests/test_property.py).
Shapes deliberately include non-multiples of 128, budget-1 SV sets,
and empty/all-padded sorted-id buffers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_backend_parity

from repro.core import engine
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rff import RFFSpec
from repro.core.rkhs import KernelSpec, SVModel
from repro.core.substrate import LinearSubstrate, RFFSubstrate, SVSubstrate
from repro.kernels import ops

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container without hypothesis: CI has it
    HAVE_HYPOTHESIS = False


def _sv_sub(budget, d=7, kind="gaussian", backend="reference"):
    lcfg = LearnerConfig(algo="kernel_sgd", budget=budget, dim=d,
                         kernel=KernelSpec(kind=kind, gamma=0.4))
    return SVSubstrate(lcfg=lcfg, backend=backend)


def _stacked_models(seed, m, budget, d, active_frac=0.8):
    """A stacked SVModel with ``active_frac`` of slots active; inactive
    slots follow the repo convention (sv_id=-1, zeroed payload)."""
    rng = np.random.default_rng(seed)
    sv = rng.normal(size=(m, budget, d)).astype(np.float32)
    alpha = rng.normal(size=(m, budget)).astype(np.float32)
    active = rng.random((m, budget)) < active_frac
    ids = np.arange(m * budget, dtype=np.int32).reshape(m, budget)
    ids = np.where(active, ids, -1)
    sv = np.where(active[..., None], sv, 0.0)
    alpha = np.where(active, alpha, 0.0)
    return SVModel(sv=jnp.asarray(sv), alpha=jnp.asarray(alpha),
                   sv_id=jnp.asarray(ids, jnp.int32))


def _one_model(seed, budget, d, active_frac=0.8):
    stacked = _stacked_models(seed, 1, budget, d, active_frac)
    return jax.tree.map(lambda v: v[0], stacked)


def _parity_pair(budget, d, kind):
    return (_sv_sub(budget, d, kind, "reference"),
            _sv_sub(budget, d, kind, "pallas"))


# budgets straddle the 128 threshold and its pad boundaries, plus the
# degenerate budget-1 set
SV_BUDGETS = [1, 31, 127, 128, 129, 200]
ACTIVE_FRACS = [0.0, 0.8, 1.0]      # 0.0 = all-padded sorted-id buffer


class TestSVParity:
    @pytest.mark.parametrize("budget", SV_BUDGETS)
    @pytest.mark.parametrize("kind", ["gaussian", "linear", "poly"])
    def test_predict(self, budget, kind):
        ref_sub, pal_sub = _parity_pair(budget, 7, kind)
        models = _stacked_models(1, 3, budget, 7)
        x = jnp.asarray(
            np.random.default_rng(2).normal(size=(3, 7)), jnp.float32)
        want = ref_sub.predict(models, x)
        got = pal_sub.predict(models, x)
        assert_backend_parity(got, want, f"predict b={budget} {kind}")
        if not ops.engages(budget):
            assert np.array_equal(np.asarray(got), np.asarray(want)), (
                "sub-threshold pallas must be bit-identical")

    @pytest.mark.parametrize("budget", SV_BUDGETS)
    @pytest.mark.parametrize("frac", ACTIVE_FRACS)
    def test_predict_batch_and_rows(self, budget, frac):
        ref_sub, pal_sub = _parity_pair(budget, 7, "gaussian")
        models = _stacked_models(3, 4, budget, 7, active_frac=frac)
        rng = np.random.default_rng(4)
        lids = jnp.asarray(rng.integers(0, 4, size=11), jnp.int32)
        Xb = jnp.asarray(rng.normal(size=(11, 7)), jnp.float32)
        want = ref_sub.predict_batch(models, lids, Xb)
        got = pal_sub.predict_batch(models, lids, Xb)
        assert_backend_parity(got, want, f"predict_batch b={budget}")
        if frac == 0.0:      # empty expansions predict exactly zero
            assert np.array_equal(np.asarray(got), np.zeros(11, np.float32))
        # the serving contract on the fused path: each batch row is
        # bitwise the lone predict_one of its home model
        rows = np.asarray(got)
        for i in [0, 5, 10]:
            one = pal_sub.predict_one(
                jax.tree.map(lambda v: v[lids[i]], models), Xb[i])
            assert rows[i] == float(one), (
                f"row {i} differs from predict_one at b={budget}")

    @pytest.mark.parametrize("budget", [1, 31, 129])
    def test_dist_and_divergence(self, budget):
        ref_sub, pal_sub = _parity_pair(budget, 7, "gaussian")
        models = _stacked_models(5, 3, budget, 7)
        ref_model = _one_model(6, budget, 7)
        want = ref_sub.dist_to_ref(models, ref_model)
        got = pal_sub.dist_to_ref(models, ref_model)
        assert_backend_parity(got, want, f"dist_to_ref b={budget}")
        want_d = ref_sub.divergence(models)
        got_d = pal_sub.divergence(models)
        assert_backend_parity(got_d, want_d, f"divergence b={budget}")
        if not ops.engages(budget):
            assert np.array_equal(np.asarray(got), np.asarray(want))
            assert float(got_d) == float(want_d)

    def test_dist_one_straddle_picks_per_term(self):
        """A learner budget above the threshold beside a sync budget
        below it: dist_one engages, and rkhs_dist_sq launches the fused
        quadform for exactly the two terms whose extents engage
        (<f, f> at 129 x 129 and <f, r> at 129 x 64); <r, r> at 64 x 64
        runs the reference quadratic form."""
        lcfg = LearnerConfig(algo="kernel_sgd", budget=129, dim=7,
                             kernel=KernelSpec(kind="gaussian", gamma=0.4))
        ref_sub = SVSubstrate(lcfg=lcfg, sync_budget=64)
        pal_sub = dataclasses.replace(ref_sub, backend="pallas")
        model, ref_model = _one_model(11, 129, 7), _one_model(12, 64, 7)
        before = ops.TRACE_COUNTS["quadform"]
        got = pal_sub.dist_one(model, ref_model)
        assert ops.TRACE_COUNTS["quadform"] == before + 2
        assert_backend_parity(got, ref_sub.dist_one(model, ref_model),
                              "straddled dist_one")

    @pytest.mark.parametrize("budget", [1, 129])
    def test_round_stacked(self, budget):
        ref_sub, pal_sub = _parity_pair(budget, 7, "gaussian")
        m = 3
        state = ref_sub.init(m)
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(m, 7)), jnp.float32)
        y = jnp.asarray(rng.choice([-1.0, 1.0], size=(m,)), jnp.float32)
        # a few warm rounds so the models are non-trivial
        for _ in range(4):
            state, _, _ = ref_sub.round_stacked(state, (x, y))
        s_ref, l_ref, y_ref = ref_sub.round_stacked(state, (x, y))
        s_pal, l_pal, y_pal = pal_sub.round_stacked(state, (x, y))
        assert_backend_parity(y_pal, y_ref, "round yhat")
        assert_backend_parity(l_pal, l_ref, "round losses")
        assert_backend_parity(s_pal.model.alpha, s_ref.model.alpha,
                              "round alphas")
        # the fused round must also equal the composed predict + update
        yhat_c = ref_sub.predict(state.model, x)
        s_c, l_c = ref_sub.update(state, (x, y))
        assert np.array_equal(np.asarray(yhat_c), np.asarray(y_ref))
        assert np.array_equal(np.asarray(l_c), np.asarray(l_ref))
        assert np.array_equal(np.asarray(s_c.model.alpha),
                              np.asarray(s_ref.model.alpha))


RFF_FEATURES = [32, 127, 128, 129, 256]


class TestRFFParity:
    @pytest.mark.parametrize("D", RFF_FEATURES)
    def test_predict_and_batch(self, D):
        ref_sub = RFFSubstrate(spec=RFFSpec(dim=6, num_features=D, seed=0))
        pal_sub = dataclasses.replace(ref_sub, backend="pallas")
        m = 3
        rng = np.random.default_rng(8)
        models = jax.tree.map(
            jnp.asarray,
            type(ref_sub.init(m))(
                w=jnp.asarray(rng.normal(size=(m, D)), jnp.float32),
                b=jnp.asarray(rng.normal(size=(m,)), jnp.float32)))
        x = jnp.asarray(rng.normal(size=(m, 6)), jnp.float32)
        want = ref_sub.predict(models, x)
        got = pal_sub.predict(models, x)
        assert_backend_parity(got, want, f"rff predict D={D}")
        lids = jnp.asarray(rng.integers(0, m, size=9), jnp.int32)
        Xb = jnp.asarray(rng.normal(size=(9, 6)), jnp.float32)
        assert_backend_parity(pal_sub.predict_batch(models, lids, Xb),
                              ref_sub.predict_batch(models, lids, Xb),
                              f"rff predict_batch D={D}")

    @pytest.mark.parametrize("D", [32, 129, 256])
    @pytest.mark.parametrize("loss", ["hinge", "squared"])
    def test_round_stacked(self, D, loss):
        ref_sub = RFFSubstrate(spec=RFFSpec(dim=6, num_features=D, seed=0),
                               loss=loss)
        pal_sub = dataclasses.replace(ref_sub, backend="pallas")
        m = 3
        state = ref_sub.init(m)
        rng = np.random.default_rng(9)
        for i in range(3):
            x = jnp.asarray(rng.normal(size=(m, 6)), jnp.float32)
            y = jnp.asarray(rng.choice([-1.0, 1.0], size=(m,)), jnp.float32)
            state, _ = ref_sub.update(state, (x, y))
        x = jnp.asarray(rng.normal(size=(m, 6)), jnp.float32)
        y = jnp.asarray(rng.choice([-1.0, 1.0], size=(m,)), jnp.float32)
        s_ref, l_ref, y_ref = ref_sub.round_stacked(state, (x, y))
        s_pal, l_pal, y_pal = pal_sub.round_stacked(state, (x, y))
        assert_backend_parity(y_pal, y_ref, f"rff round yhat D={D}")
        assert_backend_parity(l_pal, l_ref, f"rff round loss D={D}")
        assert_backend_parity(s_pal.w, s_ref.w, f"rff round w D={D}")
        assert_backend_parity(s_pal.b, s_ref.b, f"rff round b D={D}")
        # unfused reference round == composed predict + update, bitwise
        yhat_c = ref_sub.predict(state, x)
        s_c, l_c = ref_sub.update(state, (x, y))
        assert np.array_equal(np.asarray(yhat_c), np.asarray(y_ref))
        assert np.array_equal(np.asarray(l_c), np.asarray(l_ref))
        assert np.array_equal(np.asarray(s_c.w), np.asarray(s_ref.w))


class TestEngineParity:
    """End-to-end: the scan engine's observables across backends."""

    def _stream(self, T=50, m=3, d=8, seed=0):
        rng = np.random.default_rng(seed)
        X = np.asarray(rng.normal(size=(T, m, d)), np.float32)
        Y = np.asarray(rng.choice([-1.0, 1.0], size=(T, m)), np.float32)
        return X, Y

    @pytest.mark.parametrize("kind", ["periodic", "dynamic"])
    @pytest.mark.parametrize("learner", ["sv", "rff32", "linear"])
    def test_small_sv_bitwise(self, learner, kind):
        """Below the threshold the substrate alone decides to run the
        reference expressions, so the pallas engine is bitwise the
        reference engine for every substrate."""
        X, Y = self._stream()
        sub = {"sv": lambda: _sv_sub(32, 8),
               "rff32": lambda: RFFSubstrate(
                   spec=RFFSpec(dim=8, num_features=32, seed=0)),
               "linear": lambda: LinearSubstrate(
                   lcfg=LearnerConfig(algo="linear_sgd", dim=8))}[learner]()
        pcfg = ProtocolConfig(kind=kind, period=10, delta=1.0, mini_batch=5)
        r_ref = engine.run(sub, pcfg, X, Y)
        r_pal = engine.run(dataclasses.replace(sub, backend="pallas"),
                           pcfg, X, Y)
        assert np.array_equal(r_ref.cumulative_loss, r_pal.cumulative_loss)
        assert np.array_equal(r_ref.cumulative_errors,
                              r_pal.cumulative_errors)
        assert int(r_ref.total_bytes) == int(r_pal.total_bytes)
        assert r_ref.num_syncs == r_pal.num_syncs

    def test_engaged_sv_parity(self):
        X, Y = self._stream(T=30)
        sub = _sv_sub(130, 8)
        pcfg = ProtocolConfig(kind="periodic", period=10)
        r_ref = engine.run(sub, pcfg, X, Y)
        r_pal = engine.run(dataclasses.replace(sub, backend="pallas"),
                           pcfg, X, Y)
        assert_backend_parity(r_pal.cumulative_loss, r_ref.cumulative_loss,
                              "engaged SV engine losses")
        assert int(r_ref.total_bytes) == int(r_pal.total_bytes)

    def test_engaged_rff_parity(self):
        X, Y = self._stream(T=40)
        sub = RFFSubstrate(spec=RFFSpec(dim=8, num_features=256, seed=0))
        pcfg = ProtocolConfig(kind="dynamic", delta=1.0, mini_batch=5)
        r_ref = engine.run(sub, pcfg, X, Y)
        r_pal = engine.run(dataclasses.replace(sub, backend="pallas"),
                           pcfg, X, Y)
        assert_backend_parity(r_pal.cumulative_loss, r_ref.cumulative_loss,
                              "engaged RFF engine losses")
        assert int(r_ref.total_bytes) == int(r_pal.total_bytes)
        assert r_ref.num_syncs == r_pal.num_syncs


# ---------------------------------------------------------------------------
# Property-based shape sweep (hypothesis; CI always installs it)
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(budget=st.integers(1, 160), d=st.integers(1, 16),
           m=st.integers(1, 4), seed=st.integers(0, 2**16),
           frac=st.sampled_from([0.0, 0.5, 1.0]),
           kind=st.sampled_from(["gaussian", "linear", "poly"]))
    def test_sv_parity_sweep(budget, d, m, seed, frac, kind):
        ref_sub, pal_sub = _parity_pair(budget, d, kind)
        models = _stacked_models(seed, m, budget, d, active_frac=frac)
        rng = np.random.default_rng(seed + 1)
        x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        want = ref_sub.predict(models, x)
        got = pal_sub.predict(models, x)
        assert_backend_parity(got, want, f"sweep predict b={budget} d={d}")
        if not ops.engages(budget):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        lids = jnp.asarray(rng.integers(0, m, size=6), jnp.int32)
        Xb = jnp.asarray(rng.normal(size=(6, d)), jnp.float32)
        assert_backend_parity(pal_sub.predict_batch(models, lids, Xb),
                              ref_sub.predict_batch(models, lids, Xb),
                              f"sweep batch b={budget} d={d}")
        ref_model = _one_model(seed + 2, budget, d, active_frac=max(frac, 0.5))
        assert_backend_parity(pal_sub.dist_to_ref(models, ref_model),
                              ref_sub.dist_to_ref(models, ref_model),
                              f"sweep dist b={budget} d={d}")

    @settings(max_examples=8, deadline=None)
    @given(D=st.integers(1, 200), d=st.integers(1, 12),
           m=st.integers(1, 4), seed=st.integers(0, 2**16))
    def test_rff_parity_sweep(D, d, m, seed):
        ref_sub = RFFSubstrate(spec=RFFSpec(dim=d, num_features=D, seed=0))
        pal_sub = dataclasses.replace(ref_sub, backend="pallas")
        rng = np.random.default_rng(seed)
        state = type(ref_sub.init(m))(
            w=jnp.asarray(rng.normal(size=(m, D)), jnp.float32),
            b=jnp.asarray(rng.normal(size=(m,)), jnp.float32))
        x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        y = jnp.asarray(rng.choice([-1.0, 1.0], size=(m,)), jnp.float32)
        assert_backend_parity(pal_sub.predict(state, x),
                              ref_sub.predict(state, x),
                              f"rff sweep predict D={D}")
        s_ref, l_ref, y_ref = ref_sub.round_stacked(state, (x, y))
        s_pal, l_pal, y_pal = pal_sub.round_stacked(state, (x, y))
        assert_backend_parity(y_pal, y_ref, f"rff sweep yhat D={D}")
        assert_backend_parity(s_pal.w, s_ref.w, f"rff sweep w D={D}")
        if not ops.engages(m, D):
            assert np.array_equal(np.asarray(y_pal), np.asarray(y_ref))
            assert np.array_equal(np.asarray(s_pal.w), np.asarray(s_ref.w))
