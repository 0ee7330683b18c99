"""The matrix products of the references, at a named float32 precision.

``highest`` is float32 throughout (six bfloat16 passes on a TPU's
matrix unit).  ``high`` is the next precision below, three bfloat16
passes: each operand is split into a bfloat16 head and a bfloat16 tail
and the tail-by-tail product is dropped.  It is written out here, not
left to ``Precision.HIGH``, so that it means the same on every backend
(the CPU ignores the precision flag); it serves as the control that the
correctness comparison has to fail.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

MODES = ("highest", "high")


def _mm(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _split(a):
    head = a.astype(jnp.bfloat16).astype(jnp.float32)
    tail = (a - head).astype(jnp.bfloat16).astype(jnp.float32)
    return head, tail


def dot(a, b, mode: str):
    """``a @ b`` (batched like ``jnp.matmul``) at precision ``mode``."""
    if mode == "highest":
        return _mm(a, b)
    if mode == "high":
        ah, at = _split(a)
        bh, bt = _split(b)
        return _mm(ah, bh) + (_mm(ah, bt) + _mm(at, bh))
    raise ValueError(f"unknown precision {mode!r}; expected one of {MODES}")
