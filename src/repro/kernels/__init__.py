"""Pallas TPU kernels for the paper compute hot-spots.

``ops`` is the public face (padding, launch, interpret-mode
selection); ``fused`` holds the per-round fused kernels; ``ref`` the
jnp oracles.
"""
from . import fused, ops, ref

__all__ = ["fused", "ops", "ref"]
