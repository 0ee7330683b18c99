"""Fused Random-Fourier-Features Pallas kernel (TPU target).

Z = sqrt(2/D) * cos(X W^T + b)

The projection X W^T is an MXU matmul; the bias add, cosine and scale
are fused on the VPU so the pre-activation matrix never round-trips to
HBM.  Grid: (ceil(M/bm), ceil(D/bd)); each program computes one
(bm, bd) feature tile from a (bm, d) input slab and a (bd, d) weight
slab resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BD = 128


def _rff_kernel(x_ref, w_ref, b_ref, o_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32)            # (bm, d)
    w = w_ref[...].astype(jnp.float32)            # (bd, d)
    b = b_ref[...].astype(jnp.float32)            # (1, bd)
    proj = jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                             # (bm, bd)
    o_ref[...] = scale * jnp.cos(proj + b)


def rff_pallas(
    X: jnp.ndarray,      # (M, d)
    W: jnp.ndarray,      # (D, d)
    b: jnp.ndarray,      # (D,)
    *,
    num_features: int | None = None,
    block_m: int = DEFAULT_BM,
    block_d: int = DEFAULT_BD,
    interpret: bool = False,
) -> jnp.ndarray:
    M, d = X.shape
    D, _ = W.shape
    assert M % block_m == 0 and D % block_d == 0, (M, D, block_m, block_d)
    import math
    scale = math.sqrt(2.0 / (num_features or D))
    kernel = functools.partial(_rff_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, D // block_d),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_d, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        interpret=interpret,
    )(X, W, b.reshape(1, D))
