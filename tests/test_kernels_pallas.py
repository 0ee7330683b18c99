"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp
oracle, swept over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rkhs import KernelSpec
from repro.kernels import ops, ref

SHAPES = [(128, 128, 8), (256, 384, 130), (100, 200, 7), (513, 129, 64),
          (64, 64, 3)]
KINDS = ["gaussian", "linear", "poly"]
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(M, N, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(M, d)), dtype)
    Y = jnp.asarray(rng.normal(size=(N, d)), dtype)
    a = jnp.asarray(rng.normal(size=(M,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    return X, Y, a, b


@pytest.mark.parametrize("M,N,d", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_gram_matches_oracle(M, N, d, kind):
    X, Y, _, _ = _data(M, N, d, jnp.float32)
    got = ops.gram(KernelSpec(kind, gamma=0.5), X, Y)
    want = ref.gram_ref(X, Y, kind=kind, gamma=0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_gram_dtypes(dtype):
    X, Y, _, _ = _data(128, 128, 16, dtype)
    got = ops.gram(KernelSpec("gaussian", gamma=1.0), X, Y)
    want = ref.gram_ref(X, Y, kind="gaussian", gamma=1.0)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("M,N,d", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_quadform_matches_oracle(M, N, d, kind):
    X, Y, a, b = _data(M, N, d, jnp.float32)
    got = ops.quadform(KernelSpec(kind, gamma=0.5), X, Y, a, b)
    want = ref.quadform_ref(X, Y, a, b, kind=kind, gamma=0.5)
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=5e-3 * max(1.0, abs(float(want))))


@pytest.mark.parametrize("M,N,d", SHAPES)
def test_rff_matches_oracle(M, N, d):
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    b = jnp.asarray(rng.uniform(size=(N,)) * 6.28, jnp.float32)
    got = ops.rff_features(X, W, b)
    want = ref.rff_ref(X, W, b)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_rff_approximates_gaussian_kernel():
    """E[phi(x).phi(y)] -> k(x,y): the RFF contract (Rahimi-Recht)."""
    rng = np.random.default_rng(2)
    d, D = 4, 4096
    gamma = 0.7
    X = jnp.asarray(rng.normal(size=(16, d)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(D, d)) * np.sqrt(2 * gamma), jnp.float32)
    b = jnp.asarray(rng.uniform(size=(D,)) * 2 * np.pi, jnp.float32)
    Z = ops.rff_features(X, W, b)
    K_hat = np.asarray(Z @ Z.T)
    K = np.asarray(ref.gram_ref(X, X, kind="gaussian", gamma=gamma))
    assert np.max(np.abs(K_hat - K)) < 0.12


def test_rkhs_dist_sq_fused():
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(130, 9)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(200, 9)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(130,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(200,)), jnp.float32)
    got = ops.rkhs_dist_sq(KernelSpec("gaussian", gamma=0.5), X, Y, a, b)
    Kxx = ref.gram_ref(X, X, gamma=0.5)
    Kyy = ref.gram_ref(Y, Y, gamma=0.5)
    Kxy = ref.gram_ref(X, Y, gamma=0.5)
    want = a @ Kxx @ a + b @ Kyy @ b - 2 * (a @ Kxy @ b)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-3)


# --- substrate backend dispatch (core/substrate.py, DESIGN.md Sec. 8) -------
#
# The substrate layer's backend="pallas" routes predict / dist_to_ref /
# divergence through ops.sv_predict / rkhs_dist_sq / rff_features.
# These tests pin the interpret-mode Pallas kernels against the
# substrate's *reference* paths (the pure-jnp semantics in core/rkhs.py
# and core/rff.py), tolerance-bounded, on shapes large enough that the
# substrate engages the Pallas launch (>= 128, see ops._MIN_PALLAS).


def _sv_fixture(m=2, budget=130, d=9, seed=5):
    from repro.core.learners import LearnerConfig
    from repro.core.rkhs import KernelSpec, SVModel
    from repro.core.substrate import SVSubstrate
    rng = np.random.default_rng(seed)

    def one():
        active = rng.random(budget) < 0.8
        return SVModel(
            sv=jnp.asarray(rng.normal(size=(budget, d)), jnp.float32),
            alpha=jnp.asarray(rng.normal(size=(budget,)), jnp.float32),
            sv_id=jnp.asarray(np.where(active, np.arange(budget), -1),
                              jnp.int32))

    models = SVModel(*[jnp.stack(parts) for parts in
                       zip(*[tuple(one()) for _ in range(m)])])
    ref_model = one()
    lcfg = LearnerConfig(algo="kernel_sgd", budget=budget,
                         kernel=KernelSpec("gaussian", gamma=0.4), dim=d)
    return (SVSubstrate(lcfg=lcfg),
            SVSubstrate(lcfg=lcfg, backend="pallas"),
            models, ref_model, rng)


def test_substrate_predict_pallas_vs_reference():
    s_ref, s_pal, models, _, rng = _sv_fixture()
    x = jnp.asarray(rng.normal(size=(2, 9)), jnp.float32)
    got = s_pal.predict(models, x)
    want = s_ref.predict(models, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_substrate_dist_to_ref_pallas_vs_reference():
    s_ref, s_pal, models, ref_model, _ = _sv_fixture()
    got = s_pal.dist_to_ref(models, ref_model)
    want = s_ref.dist_to_ref(models, ref_model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-3)


def test_substrate_divergence_pallas_vs_reference():
    s_ref, s_pal, models, _, _ = _sv_fixture()
    got, want = s_pal.divergence(models), s_ref.divergence(models)
    np.testing.assert_allclose(float(got), float(want), rtol=5e-4, atol=5e-3)


def test_substrate_rff_features_pallas_vs_reference():
    from repro.core.rff import RFFSpec
    from repro.core.substrate import RFFSubstrate
    spec = RFFSpec(dim=8, num_features=256, gamma=0.5, seed=1)
    s_ref = RFFSubstrate(spec=spec)
    s_pal = RFFSubstrate(spec=spec, backend="pallas")
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.normal(size=(140, 8)), jnp.float32)
    np.testing.assert_allclose(np.asarray(s_pal._phi(X)),
                               np.asarray(s_ref._phi(X)),
                               rtol=2e-5, atol=2e-5)


def test_spec_entry_points_vs_substrate_reference():
    """ops.gram / quadform / rkhs_dist_sq on a KernelSpec, against the
    rkhs.py reference algebra the substrates use by default."""
    from repro.core import rkhs
    from repro.core.rkhs import KernelSpec
    spec = KernelSpec("gaussian", gamma=0.7)
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.normal(size=(130, 6)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(150, 6)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(130,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(150,)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.gram(spec, X, Y)),
        np.asarray(rkhs.gram(spec, X, Y)), rtol=2e-5, atol=2e-5)
    want_qf = float(a @ rkhs.gram(spec, X, Y) @ b)
    got_qf = float(ops.quadform(spec, X, Y, a, b))
    np.testing.assert_allclose(got_qf, want_qf, rtol=5e-4,
                               atol=5e-3 * max(1.0, abs(want_qf)))
    fa = rkhs.SVModel(sv=X, alpha=a, sv_id=jnp.arange(130, dtype=jnp.int32))
    fb = rkhs.SVModel(sv=Y, alpha=b,
                      sv_id=jnp.arange(130, 280, dtype=jnp.int32))
    np.testing.assert_allclose(
        float(ops.rkhs_dist_sq(spec, X, Y, a, b)),
        float(rkhs.dist_sq(spec, fa, fb)), rtol=1e-4, atol=1e-3)


# --- flash attention (kernels/flash.py) -------------------------------------

def _flash_ref(q, k, v, causal=True):
    hd = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    if causal:
        S, L = s.shape[-2:]
        m = jnp.arange(L)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(m[None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32))


import jax  # noqa: E402


@pytest.mark.parametrize("S,hd,bq,bk", [(256, 64, 64, 64), (128, 128, 128, 64),
                                        (384, 64, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(S, hd, bq, bk, causal):
    from repro.kernels.flash import flash_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(3, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, S, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = _flash_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash import flash_attention
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = _flash_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("window", [32, 100, 64])
def test_flash_attention_sliding_window(window):
    from repro.kernels.flash import flash_attention
    rng = np.random.default_rng(2)
    S, hd = 256, 64
    q = jnp.asarray(rng.normal(size=(2, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, S, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    # oracle: masked softmax with the band mask
    s = jnp.einsum("bqd,bkd->bqk", q, k) * (hd ** -0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    m = (kpos <= qpos) & (kpos > qpos - window)
    s = jnp.where(m[None], s, -1e30)
    want = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
