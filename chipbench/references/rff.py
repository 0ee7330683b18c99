"""Plain reference of m random-Fourier-feature learners under the
paper's protocols (arXiv:1911.12899 Sec. 4's proposal; Rahimi & Recht
2007), written from the description alone: it imports nothing of the
system under test.

phi(x) = sqrt(2/D) cos(W x + b) with W ~ N(0, 2 gamma I) and
b ~ U[0, 2 pi), both drawn from ``jax.random.PRNGKey(rff_seed)`` split
into (key_W, key_b).  Each learner runs hinge-loss SGD with decay on
(w, b): w <- (1 - eta lam) w - eta g phi(x), b <- b - eta g, where
g = -y if the loss is positive and 0 otherwise.  A synchronization sets
every learner to the mean model, which is also the new reference for
the local conditions ||w_i - w_r||^2 + (b_i - b_r)^2 > delta, and costs
2 m (D + 1) 4 bytes (Sec. 3 for fixed-size models).  The average is
exact, so the compression error is 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.references import precision
from chipbench.references.sv import Out


def features(cfg: dict):
    """(W (D, d), b (D,)) of the configuration's feature map."""
    kw, kb = jax.random.split(jax.random.PRNGKey(cfg["rff_seed"]))
    W = jax.random.normal(kw, (cfg["num_features"], cfg["dim"])) \
        * jnp.sqrt(2.0 * cfg["gamma"])
    b = jax.random.uniform(kb, (cfg["num_features"],), maxval=2.0 * jnp.pi)
    return W, b


@functools.lru_cache(maxsize=None)
def _program(m, D, eta, lam, kind, mode):
    decay = np.float32(1.0 - eta * lam)
    scale = np.float32(np.sqrt(2.0 / D))
    sync_bytes = 2 * m * (D + 1) * 4

    def step(carry, xs, W, phase):
        w, b, rw, rb = carry
        x, y, t, delta, period = xs
        z = scale * jnp.cos(precision.dot(x, W.T, mode) + phase)      # (m, D)
        yhat = precision.dot(z[:, None, :], w[:, :, None], mode)[:, 0, 0] + b
        loss = jnp.maximum(0.0, 1.0 - y * yhat)
        err = (jnp.where(yhat >= 0, 1.0, -1.0) != y).astype(jnp.float32)
        g = jnp.where(loss > 0.0, -y, 0.0)
        w = decay * w - (eta * g)[:, None] * z
        b = b - eta * g
        if kind == "dynamic":
            dist = jnp.sum((w - rw[None]) ** 2, axis=-1) + (b - rb) ** 2
            top = jnp.max(dist)
            do_sync = top > delta
            dist_amb = jnp.abs(top - delta)
        else:
            do_sync = ((t + 1) % period) == 0
            dist_amb = jnp.asarray(jnp.inf, jnp.float32)

        def sync(args):
            w, b, _, _ = args
            mw, mb = jnp.mean(w, axis=0), jnp.mean(b)
            return (jnp.broadcast_to(mw[None], w.shape), jnp.full_like(b, mb),
                    mw, mb), jnp.asarray(sync_bytes, jnp.int32)

        def no_sync(args):
            return args, jnp.zeros((), jnp.int32)

        carry, nbytes = lax.cond(do_sync, sync, no_sync, (w, b, rw, rb))
        out = (loss, err, nbytes, do_sync, jnp.min(jnp.abs(1.0 - y * yhat)),
               jnp.min(jnp.abs(yhat)), dist_amb, jnp.zeros((), jnp.float32))
        return carry, out

    @jax.jit
    def simulate(X, Y, W, phase, delta, period):
        T = X.shape[0]
        carry = (jnp.zeros((m, D), jnp.float32), jnp.zeros((m,), jnp.float32),
                 jnp.zeros((D,), jnp.float32), jnp.zeros((), jnp.float32))
        xs = (X, Y, jnp.arange(T, dtype=jnp.int32),
              jnp.full((T,), delta, jnp.float32), jnp.full((T,), period, jnp.int32))
        return lax.scan(functools.partial(step, W=W, phase=phase), carry, xs)[1]

    return simulate


def run(cfg: dict, protocol: dict, X: np.ndarray, Y: np.ndarray,
        mode: str = "highest") -> Out:
    """The reference's per-round outputs for one stream (T, m, d)."""
    T, m, d = X.shape
    kind = protocol["kind"]
    if kind not in ("dynamic", "periodic") or protocol.get("mini_batch", 1) != 1:
        raise ValueError(f"the RFF reference runs dynamic (mini_batch 1) or "
                         f"periodic protocols, not {protocol}")
    W, phase = features(cfg)
    sim = _program(m, cfg["num_features"], float(cfg["eta"]), float(cfg["lam"]),
                   kind, mode)
    outs = sim(jnp.asarray(X), jnp.asarray(Y), W, phase,
               protocol.get("delta", 0.0), protocol.get("period", 1))
    return Out(*(np.asarray(o) for o in outs))
