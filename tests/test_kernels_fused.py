"""Fused kernels, fallback boundaries, and the block-size autotuner.

Three contracts from ISSUE 7:

- fallback boundary: shapes below ``ops._MIN_PALLAS`` take the jnp
  reference path bit-for-bit (and never launch); ``force_pallas=True``
  on the same shapes still matches within the pinned parity tolerance;
  ``_pad_to`` cropping is exact at n = mult +/- 1 for every kernel
  kind;
- fused kernels equal their oracles (kernels/ref.py) for all kernel
  kinds and both losses;
- the autotuner resolves deterministically off-TPU and value-equal
  configs reuse tuned blocks with ZERO new XLA compiles
  (telemetry.probe.CompileCounter) — the recompile-regression gate.
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
from repro.core.rkhs import KernelSpec
from repro.core.substrate import SVSubstrate
from repro.kernels import autotune, ops, ref
from repro.telemetry.probe import CompileCounter

KINDS = ["gaussian", "linear", "poly"]


def _rng(seed=0):
    return np.random.default_rng(seed)


def _sv_args(rng, B, N, d):
    X = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    SV = jnp.asarray(rng.normal(size=(B, N, d)), jnp.float32)
    A = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    mask = jnp.asarray(rng.random((B, N)) < 0.8, jnp.float32)
    return X, SV, A * mask


def _step_args(rng, B, d, D=None):
    X = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    Y = jnp.asarray(rng.choice([-1.0, 1.0], size=(B,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, D or d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B,)), jnp.float32)
    kw = {}
    if D is not None:
        kw["W"] = jnp.asarray(rng.normal(size=(D, d)), jnp.float32)
        kw["bias"] = jnp.asarray(
            rng.uniform(0, 2 * np.pi, size=(D,)), jnp.float32)
        kw["scale"] = float(np.sqrt(2.0 / D))
    return (X, Y, w, b), kw


# ---------------------------------------------------------------------------
# Fused kernels vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("N", [127, 129, 256])
def test_sv_predict_matches_oracle(kind, N):
    X, SV, A = _sv_args(_rng(1), 4, N, 9)
    want = ref.sv_predict_ref(X, SV, A, kind=kind, gamma=0.5)
    got = ops.sv_predict(X, SV, A, kind=kind, gamma=0.5, force_pallas=True)
    assert got.shape == (4,)
    assert_backend_parity(got, want, f"sv_predict {kind} N={N}")


@pytest.mark.parametrize("loss", ["hinge", "squared"])
@pytest.mark.parametrize("B", [127, 129])
def test_fused_rff_step_matches_oracle(loss, B):
    args, kw = _step_args(_rng(2), B, 9, D=140)
    want = ref.primal_step_ref(*args, loss=loss, eta=0.3, lam=0.01, **kw)
    got = ops.fused_primal_step(*args, loss=loss, eta=0.3, lam=0.01,
                                force_pallas=True, **kw)
    for g, w, name in zip(got, want, ["w", "b", "ell", "yhat"]):
        assert_backend_parity(g, w, f"rff_step/{name} {loss} B={B}")


@pytest.mark.parametrize("B", [127, 129])
def test_fused_linear_step_matches_oracle(B):
    args, _ = _step_args(_rng(3), B, 9)
    want = ref.primal_step_ref(*args, loss="hinge", eta=0.3, lam=0.01)
    got = ops.fused_primal_step(*args, loss="hinge", eta=0.3, lam=0.01,
                                force_pallas=True)
    for g, w, name in zip(got, want, ["w", "b", "ell", "yhat"]):
        assert_backend_parity(g, w, f"linear_step/{name} B={B}")


# ---------------------------------------------------------------------------
# Fallback boundary
# ---------------------------------------------------------------------------


def test_engages_threshold():
    assert not ops.engages(1)
    assert not ops.engages(127, 100)
    assert ops.engages(128)
    assert ops.engages(2, 128)


def test_below_min_pallas_is_reference_bitwise():
    """Sub-threshold calls return the jnp oracle's exact floats and
    never count a launch."""
    rng = _rng(4)
    X = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(30, 9)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(40,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(30,)), jnp.float32)
    Xs, SVs, As = _sv_args(rng, 3, 40, 9)
    sargs, skw = _step_args(rng, 5, 9, D=40)
    before = dict(ops.LAUNCH_COUNTS)
    checks = [
        (ops.gram(X, Y, gamma=0.5), ref.gram_ref(X, Y, gamma=0.5)),
        (ops.quadform(X, Y, a, b, gamma=0.5),
         ref.quadform_ref(X, Y, a, b, gamma=0.5)),
        (ops.sv_predict(Xs, SVs, As, gamma=0.5),
         ref.sv_predict_ref(Xs, SVs, As, gamma=0.5)),
    ]
    got_step = ops.fused_primal_step(*sargs, loss="hinge", **skw)
    want_step = ref.primal_step_ref(*sargs, loss="hinge", **skw)
    checks += list(zip(got_step, want_step))
    for got, want in checks:
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert dict(ops.LAUNCH_COUNTS) == before, "fallback must not launch"


def test_force_pallas_on_small_shapes_is_close():
    rng = _rng(5)
    Xs, SVs, As = _sv_args(rng, 3, 40, 9)
    assert_backend_parity(
        ops.sv_predict(Xs, SVs, As, gamma=0.5, force_pallas=True),
        ref.sv_predict_ref(Xs, SVs, As, gamma=0.5), "forced small sv")
    sargs, skw = _step_args(rng, 5, 9, D=40)
    got = ops.fused_primal_step(*sargs, loss="hinge", force_pallas=True,
                                **skw)
    want = ref.primal_step_ref(*sargs, loss="hinge", **skw)
    for g, w in zip(got, want):
        assert_backend_parity(g, w, "forced small step")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [127, 129])
def test_pad_crop_exact_every_kind(kind, n):
    """n = mult +/- 1 exercises both pad directions; outputs must crop
    back to exactly the unpadded extents with oracle-close values."""
    rng = _rng(6)
    X = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    K = ops.gram(X, Y, kind=kind, gamma=0.5, force_pallas=True)
    assert K.shape == (n, n)
    np.testing.assert_allclose(
        np.asarray(K),
        np.asarray(ref.gram_ref(X, Y, kind=kind, gamma=0.5)),
        rtol=2e-5, atol=2e-5)
    q = ops.quadform(X, Y, a, b, kind=kind, gamma=0.5, force_pallas=True)
    assert q.shape == ()
    assert_backend_parity(q, ref.quadform_ref(X, Y, a, b, kind=kind,
                                              gamma=0.5), f"qf {kind} {n}")


@pytest.mark.parametrize("n", [127, 129])
def test_pad_crop_exact_rff_and_fused(n):
    rng = _rng(7)
    X = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(n,)), jnp.float32)
    Z = ops.rff_features(X, W, bias, force_pallas=True)
    assert Z.shape == (n, n)
    np.testing.assert_allclose(
        np.asarray(Z), np.asarray(ref.rff_ref(X, W, bias)),
        rtol=2e-5, atol=2e-5)
    Xs, SVs, As = _sv_args(rng, 3, n, 9)
    got = ops.sv_predict(Xs, SVs, As, gamma=0.5, force_pallas=True)
    assert got.shape == (3,)
    assert_backend_parity(got, ref.sv_predict_ref(Xs, SVs, As, gamma=0.5),
                          f"sv crop {n}")
    sargs, skw = _step_args(rng, n, 9, D=n)
    got = ops.fused_primal_step(*sargs, loss="hinge", force_pallas=True,
                                **skw)
    want = ref.primal_step_ref(*sargs, loss="hinge", **skw)
    assert got[0].shape == (n, n) and got[1].shape == (n,)
    for g, w in zip(got, want):
        assert_backend_parity(g, w, f"step crop {n}")


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------


def test_autotune_candidates_and_defaults():
    assert autotune.candidates_for(100) == (128,)
    assert autotune.candidates_for(200) == (128, 256)
    assert autotune.candidates_for(600) == (128, 256, 512)
    assert autotune.default_blocks((100, 600)) == (128, 128)


def test_autotune_cache_deterministic_off_tpu():
    autotune.clear_cache()
    try:
        calls = []
        b1 = autotune.tuned_blocks(
            "op", (300, 40), kind="k",
            compile_candidate=lambda blk: calls.append(blk))
        b2 = autotune.tuned_blocks(
            "op", (300, 40), kind="k",
            compile_candidate=lambda blk: calls.append(blk))
        assert b1 == b2 == (128, 128)
        assert calls == [], "no search may run off-TPU"
        key = autotune.TileKey("op", (300, 40), "float32", "k")
        assert autotune.cache_info()[key].source == "default"
    finally:
        autotune.clear_cache()


def _fake_tpu_search(monkeypatch, refuse):
    """Run the TPU search path on the CPU: candidates in ``refuse`` are
    refused the way the TPU compiler refuses a tile."""
    monkeypatch.setattr(autotune, "_on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "_SEARCH_ITERS", 1)

    def compile_candidate(blocks):
        if blocks in refuse:
            raise ValueError(f"refused {blocks}")
        return lambda: jnp.zeros(())

    return compile_candidate


def test_autotune_search_skips_refused_candidates(monkeypatch):
    autotune.clear_cache()
    try:
        compile_candidate = _fake_tpu_search(monkeypatch, {(128,), (512,)})
        blocks = autotune.tuned_blocks("op", (600,), kind="k",
                                       compile_candidate=compile_candidate)
        assert blocks == (256,)
        key = autotune.TileKey("op", (600,), "float32", "k")
        assert autotune.cache_info()[key].source == "search"
    finally:
        autotune.clear_cache()


def test_autotune_search_raises_when_every_candidate_refused(monkeypatch):
    autotune.clear_cache()
    try:
        compile_candidate = _fake_tpu_search(
            monkeypatch, {(128,), (256,), (512,)})
        with pytest.raises(RuntimeError, match="refused every tile"):
            autotune.tuned_blocks("op", (600,), kind="k",
                                  compile_candidate=compile_candidate)
        assert autotune.cache_info() == {}
    finally:
        autotune.clear_cache()


def test_autotune_never_searches_under_a_trace(monkeypatch):
    """A traced operand means the op is being staged into an outer jit:
    the resolver takes the defaults and launches nothing."""
    autotune.clear_cache()
    try:
        calls = []
        monkeypatch.setattr(autotune, "_on_tpu", lambda: True)

        def compile_candidate(blocks):
            calls.append(blocks)
            return lambda: jnp.zeros(())

        @jax.jit
        def traced(x):
            blocks = autotune.tuned_blocks(
                "op", (600,), kind="k", compile_candidate=compile_candidate,
                operands=(x,))
            return x * blocks[0]

        assert float(traced(jnp.ones(()))) == 128.0
        assert calls == []
    finally:
        autotune.clear_cache()


def test_autotune_pin_overrides():
    autotune.clear_cache()
    try:
        autotune.pin("sv_predict", (256,), (256,), kind="gaussian:d=9")
        blocks = autotune.tuned_blocks("sv_predict", (256,),
                                       kind="gaussian:d=9")
        assert blocks == (256,)
        X, SV, A = _sv_args(_rng(8), 3, 256, 9)
        got = ops.sv_predict(X, SV, A, kind="gaussian", gamma=0.5)
        assert_backend_parity(
            got, ref.sv_predict_ref(X, SV, A, kind="gaussian", gamma=0.5),
            "pinned 256 block")
    finally:
        autotune.clear_cache()


# ---------------------------------------------------------------------------
# Recompile regression (the PR 6 compile counters as the gate)
# ---------------------------------------------------------------------------


def _pallas_sub():
    return SVSubstrate(
        lcfg=LearnerConfig(algo="kernel_sgd", budget=130, dim=8,
                           kernel=KernelSpec(kind="gaussian", gamma=0.3)),
        backend="pallas")


def test_ops_reuse_compiles_across_autotune_resets():
    """Value-equal calls hit the jit cache even after the tuner's table
    is dropped: off-TPU resolution is deterministic, so the launcher's
    static block args — and therefore its executable — are identical."""
    X, SV, A = _sv_args(_rng(9), 3, 200, 9)
    ops.sv_predict(X, SV, A, gamma=0.5)          # warm (may compile)
    with CompileCounter() as c:
        ops.sv_predict(X, SV, A, gamma=0.5)
        autotune.clear_cache()
        ops.sv_predict(X, SV, A, gamma=0.5)
    assert c.compiles == 0


def test_engine_zero_recompiles_for_value_equal_pallas_substrate():
    """Two value-equal pallas substrates are one compile-cache entry:
    the second engine.run traces and compiles NOTHING new."""
    rng = _rng(10)
    X = np.asarray(rng.normal(size=(25, 3, 8)), np.float32)
    Y = np.asarray(rng.choice([-1.0, 1.0], size=(25, 3)), np.float32)
    pcfg = ProtocolConfig(kind="periodic", period=10)
    engine.run(_pallas_sub(), pcfg, X, Y)        # warm (compiles)
    with CompileCounter() as c:
        r = engine.run(dataclasses.replace(_pallas_sub()), pcfg, X, Y)
    assert c.compiles == 0, "value-equal pallas config recompiled"
    assert np.isfinite(r.total_loss)
