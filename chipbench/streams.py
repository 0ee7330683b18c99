"""The traffic generator: a pool of seeded (X, Y) streams for a cell.

A traffic file (``chipbench/traffic/<name>.json``) names the protocol
and how many distinct streams the window cycles through (``pool``).
The configuration names the stream family, the rounds T, the learners
m and the feature width d.  Streams are drawn on the host in set-up.
"""
from __future__ import annotations

import numpy as np


def susy_stream(T: int, m: int, d: int = 8, seed=0, noise: float = 0.05):
    """Non-linearly separable binary stream in the layout of UCI SUSY's 8
    low-level features: a radial boundary in the first four features
    plus an XOR term, labels flipped with probability ``noise``."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(T, m, d)).astype(np.float32)
    r = np.sum(X[..., :4] ** 2, axis=-1)
    xor = X[..., 4] * X[..., 5]
    score = (r - 4.0) + 2.0 * xor
    flip = rng.random((T, m)) < noise
    Y = np.where((score > 0) ^ flip, 1.0, -1.0).astype(np.float32)
    return X, Y


FAMILIES = {"susy": susy_stream}


def pool(cfg: dict, traffic: dict, seed: int):
    """``traffic["pool"]`` streams of shape (T, m, d) / (T, m), the i-th
    drawn from the seed sequence (seed, i)."""
    draw = FAMILIES[cfg["stream"]]
    return [draw(cfg["rounds"], cfg["learners"], cfg["dim"], seed=(seed, i))
            for i in range(traffic["pool"])]
