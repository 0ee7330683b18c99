"""Fused kernels, small-shape launches, and compile reuse.

- the engage threshold (``ops.engages``) is the one the substrate
  dispatches on; the ops themselves launch at any shape and match
  their oracles within the pinned parity tolerance; ``_pad_to``
  cropping is exact at n = mult +/- 1 for every kernel kind;
- fused kernels equal their oracles (kernels/ref.py) for all kernel
  kinds and both losses;
- value-equal calls and configs reuse their executables with ZERO new
  XLA compiles (telemetry.probe.CompileCounter) — the
  recompile-regression gate.
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
from repro.kernels import ops, ref
from repro.telemetry.probe import CompileCounter

KINDS = ["gaussian", "linear", "poly"]
SPEC = KernelSpec("gaussian", gamma=0.5)


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
    got = ops.sv_predict(KernelSpec(kind, gamma=0.5), X, SV, A)
    assert got.shape == (4,)
    assert_backend_parity(got, want, f"sv_predict {kind} N={N}")


@pytest.mark.parametrize("loss", ["hinge", "squared"])
@pytest.mark.parametrize("B", [127, 129])
def test_fused_rff_step_matches_oracle(loss, B):
    args, kw = _step_args(_rng(2), B, 9, D=140)
    want = ref.primal_step_ref(*args, loss=loss, eta=0.3, lam=0.01, **kw)
    got = ops.fused_primal_step(*args, loss=loss, eta=0.3, lam=0.01, **kw)
    for g, w, name in zip(got, want, ["w", "b", "ell", "yhat"]):
        assert_backend_parity(g, w, f"rff_step/{name} {loss} B={B}")


@pytest.mark.parametrize("B", [127, 129])
def test_fused_linear_step_matches_oracle(B):
    args, _ = _step_args(_rng(3), B, 9)
    want = ref.primal_step_ref(*args, loss="hinge", eta=0.3, lam=0.01)
    got = ops.fused_primal_step(*args, loss="hinge", eta=0.3, lam=0.01)
    for g, w, name in zip(got, want, ["w", "b", "ell", "yhat"]):
        assert_backend_parity(g, w, f"linear_step/{name} B={B}")


# ---------------------------------------------------------------------------
# Engage threshold and small-shape launches
# ---------------------------------------------------------------------------


def test_engages_threshold():
    assert not ops.engages(1)
    assert not ops.engages(127, 100)
    assert ops.engages(128)
    assert ops.engages(2, 128)


def _small_launches():
    """One small-shape call of each public op that launches a kernel:
    {op name in TRACE_COUNTS: thunk returning (got, want)}."""
    rng = _rng(5)
    X = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(30, 9)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(40,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(30,)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(30, 9)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(30,)), jnp.float32)
    Xs, SVs, As = _sv_args(rng, 3, 40, 9)
    sargs, skw = _step_args(rng, 5, 9, D=40)
    return {
        "gram": lambda: (ops.gram(SPEC, X, Y), ref.gram_ref(X, Y, gamma=0.5)),
        "quadform": lambda: (ops.quadform(SPEC, X, Y, a, b),
                             ref.quadform_ref(X, Y, a, b, gamma=0.5)),
        "rff": lambda: (ops.rff_features(X, W, bias),
                        ref.rff_ref(X, W, bias)),
        "sv_predict": lambda: (ops.sv_predict(SPEC, Xs, SVs, As),
                               ref.sv_predict_ref(Xs, SVs, As, gamma=0.5)),
        "rff_step": lambda: (ops.fused_primal_step(*sargs, loss="hinge",
                                                   **skw),
                             ref.primal_step_ref(*sargs, loss="hinge",
                                                 **skw)),
    }


@pytest.mark.parametrize("op", ["gram", "quadform", "rff", "sv_predict",
                                "rff_step"])
def test_ops_launch_at_small_shapes(op):
    """An op launches its kernel at any shape: the engage decision is
    the caller's (core.substrate), so below the threshold the op still
    pads, launches and crops, within the pinned parity tolerance."""
    before = ops.TRACE_COUNTS[op]
    got, want = _small_launches()[op]()
    assert ops.TRACE_COUNTS[op] == before + 1, f"{op} did not launch"
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert_backend_parity(g, w, f"small {op}")


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
    K = ops.gram(KernelSpec(kind, gamma=0.5), X, Y)
    assert K.shape == (n, n)
    np.testing.assert_allclose(
        np.asarray(K),
        np.asarray(ref.gram_ref(X, Y, kind=kind, gamma=0.5)),
        rtol=2e-5, atol=2e-5)
    q = ops.quadform(KernelSpec(kind, gamma=0.5), X, Y, a, b)
    assert q.shape == ()
    assert_backend_parity(q, ref.quadform_ref(X, Y, a, b, kind=kind,
                                              gamma=0.5), f"qf {kind} {n}")


@pytest.mark.parametrize("n", [127, 129])
def test_pad_crop_exact_rff_and_fused(n):
    rng = _rng(7)
    X = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    bias = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(n,)), jnp.float32)
    Z = ops.rff_features(X, W, bias)
    assert Z.shape == (n, n)
    np.testing.assert_allclose(
        np.asarray(Z), np.asarray(ref.rff_ref(X, W, bias)),
        rtol=2e-5, atol=2e-5)
    Xs, SVs, As = _sv_args(rng, 3, n, 9)
    got = ops.sv_predict(SPEC, Xs, SVs, As)
    assert got.shape == (3,)
    assert_backend_parity(got, ref.sv_predict_ref(Xs, SVs, As, gamma=0.5),
                          f"sv crop {n}")
    sargs, skw = _step_args(rng, n, 9, D=n)
    got = ops.fused_primal_step(*sargs, loss="hinge", **skw)
    want = ref.primal_step_ref(*sargs, loss="hinge", **skw)
    assert got[0].shape == (n, n) and got[1].shape == (n,)
    for g, w in zip(got, want):
        assert_backend_parity(g, w, f"step crop {n}")


# ---------------------------------------------------------------------------
# Recompile regression (the PR 6 compile counters as the gate)
# ---------------------------------------------------------------------------


def _pallas_sub():
    return SVSubstrate(
        lcfg=LearnerConfig(algo="kernel_sgd", budget=130, dim=8,
                           kernel=KernelSpec(kind="gaussian", gamma=0.3)),
        backend="pallas")


def test_value_equal_ops_calls_reuse_the_compile():
    """Value-equal calls hit the jit cache: a value-equal spec gives the
    launcher the same static args, and so the same executable."""
    X, SV, A = _sv_args(_rng(9), 3, 200, 9)
    ops.sv_predict(SPEC, X, SV, A)               # warm (may compile)
    with CompileCounter() as c:
        ops.sv_predict(SPEC, X, SV, A)
        ops.sv_predict(KernelSpec("gaussian", gamma=0.5), X, SV, A)
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
