"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies on the
CPU and cannot see what the chip's compiler refuses: block shapes that
break the (8, 128) tiling, scalar stores to VMEM.  These tests hand the
launchers ``interpret=False`` and compile them, at the shapes
chip_smoke.py runs (m=32 learners, SV budget 512, d=8 SUSY features,
1024 random Fourier features), for one chip of a described ``v5e:2x2``
topology — no chip needed.  A compiled kernel shows up as a
``tpu_custom_call`` in the compiled program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, N, d = 32, 512, 8          # learners, SV budget, SUSY features
RFF_D = 1024
KERNEL = dict(kind="gaussian", gamma=0.3, degree=3, coef0=1.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_sv_predict_compiles(one_chip):
    def fn(X, SV, A):
        return ops._sv_predict_call(X, SV, A, block_n=128, interpret=False,
                                    **KERNEL)

    _assert_kernel(_compile(fn, one_chip, (B, d), (B, N, d), (B, N)))


def _quadform(X, Y, a, b):
    return ops._quadform_call(X, Y, a, b, block_m=128, block_n=128,
                              interpret=False, **KERNEL)


def test_quadform_compiles(one_chip):
    _assert_kernel(_compile(_quadform, one_chip, (N, d), (N, d), (N,), (N,)))


def test_quadform_vmapped_over_learners_compiles(one_chip):
    """The dynamic protocol's local condition: each learner's model
    against the shared reference (``SVSubstrate.dist_to_ref``)."""
    fn = jax.vmap(_quadform, in_axes=(0, None, 0, None))
    _assert_kernel(_compile(fn, one_chip, (B, N, d), (N, d), (B, N), (N,)))


@pytest.mark.parametrize("featurize", [True, False],
                         ids=["rff_step", "linear_step"])
def test_primal_step_compiles(one_chip, featurize):
    D = RFF_D if featurize else d

    def fn(X, Yl, w, b, *rff):
        W, bias = rff if featurize else (None, None)
        return ops._primal_step_call(
            X, Yl, w, b, W, bias, scale=(2.0 / D) ** 0.5, loss="hinge",
            eta=0.5, lam=0.01, block_m=128, featurize=featurize,
            interpret=False)

    shapes = [(B, d), (B,), (B, D), (B,)]
    if featurize:
        shapes += [(D, d), (D,)]
    _assert_kernel(_compile(fn, one_chip, *shapes))


def test_gram_compiles(one_chip):
    def fn(X, Y):
        return ops._gram_call(X, Y, block_m=128, block_n=128,
                              interpret=False, **KERNEL)

    _assert_kernel(_compile(fn, one_chip, (N, d), (N, d)))
