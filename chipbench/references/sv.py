"""Plain reference of m budgeted kernel-SGD learners under the paper's
protocols (arXiv:1911.12899, Secs. 2-3), written from the description
alone: it imports nothing of the system under test.

Each learner runs NORMA (Kivinen, Smola & Williamson 2004) on its own
stream: f <- (1 - eta lam) f, plus a new support vector x with
coefficient eta y whenever the hinge loss of the prediction is
positive.  A learner holds at most ``budget`` support vectors: a new
one takes the first free slot, else the slot with the smallest
|coefficient| (the lowest slot on ties).  A synchronization averages
the m expansions (Prop. 2: all slots side by side, coefficients
divided by m), keeps the ``budget`` slots with the largest
|coefficient| (the lowest slot on ties), packs them in slot order and
gives that model to every learner; it is also the new reference for
the local conditions ||f_i - r||^2 > delta.  Bytes follow Sec. 3 with
the coordinator caching the support vectors it was sent at the last
synchronization.

Everything runs in float32.  Kernel cross terms are matrix products
at the precision the caller names (``precision.dot``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.references import precision

SENTINEL = np.iinfo(np.int32).max


class Out(NamedTuple):
    """Per-round outputs of the reference, all on the host."""

    loss: np.ndarray        # (T, m) hinge loss before the update
    err: np.ndarray         # (T, m) 1.0 where sign(yhat) != y (yhat >= 0 -> +1)
    nbytes: np.ndarray      # (T,) Sec. 3 bytes of the round's sync, else 0
    sync: np.ndarray        # (T,) bool
    margin_amb: np.ndarray  # (T,) min over learners of |1 - y yhat|
    sign_amb: np.ndarray    # (T,) min over learners of |yhat|
    dist_amb: np.ndarray    # (T,) |max_i ||f_i - r||^2 - delta| (inf if no check)
    eps: np.ndarray         # (T,) compression error of the round's sync, else 0


def _gauss(xa, xb, gamma, mode):
    """k(a, b) = exp(-gamma ||a - b||^2) over the last axis: xa (..., p, d),
    xb (..., q, d) -> (..., p, q), with the cross term a matrix product."""
    na = jnp.sum(xa * xa, axis=-1)[..., :, None]
    nb = jnp.sum(xb * xb, axis=-1)[..., None, :]
    cross = precision.dot(xa, jnp.swapaxes(xb, -1, -2), mode)
    return jnp.exp(-gamma * jnp.maximum(na + nb - 2.0 * cross, 0.0))


def _distinct(ids):
    """Number of distinct non-negative ids in each row of ``ids``."""
    s = jnp.sort(jnp.where(ids >= 0, ids, SENTINEL), axis=-1)
    first = jnp.concatenate(
        [s[..., :1] < SENTINEL, (s[..., 1:] != s[..., :-1]) & (s[..., 1:] < SENTINEL)],
        axis=-1)
    return jnp.where(first, s, SENTINEL), jnp.sum(first, axis=-1)


def _sync_bytes(ids, known, dim):
    """Sec. 3 coordinator bytes of one sync of (m, tau) id rows, and the
    new coordinator cache (sorted distinct ids of the union)."""
    b_x = dim * 4 + 4        # vector + id
    b_a = 4 + 4              # coefficient + id
    m = ids.shape[0]
    rows, n = _distinct(ids)                              # (m, tau), (m,)
    union, u = _distinct(rows.reshape(-1))
    union = jnp.sort(union)
    pos = jnp.clip(jnp.searchsorted(known, rows), 0, known.shape[0] - 1)
    in_known = jnp.sum((known[pos] == rows) & (rows < SENTINEL), axis=-1)
    total = (jnp.sum(n) * b_a + jnp.sum(n - in_known) * b_x
             + m * u * b_a + (m * u - jnp.sum(n)) * b_x)
    return total.astype(jnp.int32), union


def _truncate(sv, alpha, ids, budget):
    """Keep the ``budget`` active slots with the largest |alpha| (lowest
    slot first on ties), packed in slot order; also the kept mask."""
    act = ids >= 0
    score = jnp.where(act, jnp.abs(alpha), -jnp.inf)
    top = jnp.argsort(-score, stable=True)[:budget]
    keep = jnp.zeros(ids.shape, bool).at[top].set(True) & act
    idx = jnp.argsort(~keep, stable=True)[:budget]
    valid = keep[idx]
    return (jnp.where(valid[:, None], sv[idx], 0.0),
            jnp.where(valid, alpha[idx], 0.0),
            jnp.where(valid, ids[idx], -1)), keep


@functools.lru_cache(maxsize=None)
def _program(m, budget, dim, gamma, eta, lam, kind, mode):
    decay = np.float32(1.0 - eta * lam)

    def step(carry, xs):
        sv, alpha, ids, rsv, ralpha, rids, known = carry
        x, y, t, delta, period = xs
        a = jnp.where(ids >= 0, alpha, 0.0)
        k = _gauss(x[:, None, :], sv, gamma, mode)[:, 0, :]        # (m, tau)
        yhat = jnp.sum(k * a, axis=-1)
        loss = jnp.maximum(0.0, 1.0 - y * yhat)
        err = (jnp.where(yhat >= 0, 1.0, -1.0) != y).astype(jnp.float32)
        # NORMA: decay every coefficient, insert eta*y where the loss is positive
        alpha = alpha * decay
        free = jnp.where(ids >= 0, jnp.abs(alpha), -jnp.inf)
        slot = jnp.argmin(free, axis=-1)
        ins = loss > 0.0
        onehot = (jnp.arange(budget)[None, :] == slot[:, None]) & ins[:, None]
        new_id = t * m + jnp.arange(m, dtype=jnp.int32)
        sv = jnp.where(onehot[..., None], x[:, None, :], sv)
        alpha = jnp.where(onehot, (eta * y)[:, None], alpha)
        ids = jnp.where(onehot, new_id[:, None], ids)

        if kind == "dynamic":
            a = jnp.where(ids >= 0, alpha, 0.0)
            ra = jnp.where(rids >= 0, ralpha, 0.0)
            # a^T K b as elementwise products and sums, all in float32
            ff = jnp.sum(a[:, :, None] * _gauss(sv, sv, gamma, mode) * a[:, None, :],
                         axis=(1, 2))
            fr = jnp.sum(a[:, :, None] * _gauss(sv, rsv[None], gamma, mode)
                         * ra[None, None, :], axis=(1, 2))
            rr = jnp.sum(ra[:, None] * _gauss(rsv, rsv, gamma, mode) * ra[None, :])
            dist = ff + rr - 2.0 * fr
            top = jnp.max(dist)
            do_sync = top > delta
            dist_amb = jnp.abs(top - delta)
        else:
            do_sync = ((t + 1) % period) == 0
            dist_amb = jnp.asarray(jnp.inf, jnp.float32)

        def sync(args):
            sv, alpha, ids, rsv, ralpha, rids, known = args
            nbytes, union = _sync_bytes(ids, known, dim)
            flat_sv, flat_ids = sv.reshape(m * budget, dim), ids.reshape(-1)
            avg = jnp.where(flat_ids >= 0, alpha.reshape(-1) / m, 0.0)
            (nsv, nalpha, nids), kept = _truncate(flat_sv, avg, flat_ids, budget)
            # the compression error: the RKHS norm of the dropped part
            beta = jnp.where((flat_ids >= 0) & ~kept, avg, 0.0)
            eps2 = jnp.sum(beta[:, None] * _gauss(flat_sv, flat_sv, gamma, mode)
                           * beta[None, :])
            tile = lambda v: jnp.broadcast_to(v[None], (m,) + v.shape)
            return ((tile(nsv), tile(nalpha), tile(nids), nsv, nalpha, nids, union),
                    nbytes, jnp.sqrt(jnp.maximum(eps2, 0.0)))

        def no_sync(args):
            return args, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)

        carry, nbytes, eps = lax.cond(do_sync, sync, no_sync,
                                      (sv, alpha, ids, rsv, ralpha, rids, known))
        out = (loss, err, nbytes, do_sync, jnp.min(jnp.abs(1.0 - y * yhat)),
               jnp.min(jnp.abs(yhat)), dist_amb, eps)
        return carry, out

    @jax.jit
    def simulate(X, Y, delta, period):
        T = X.shape[0]
        carry = (jnp.zeros((m, budget, dim), jnp.float32),
                 jnp.zeros((m, budget), jnp.float32),
                 jnp.full((m, budget), -1, jnp.int32),
                 jnp.zeros((budget, dim), jnp.float32),
                 jnp.zeros((budget,), jnp.float32),
                 jnp.full((budget,), -1, jnp.int32),
                 jnp.full((m * budget,), SENTINEL, jnp.int32))
        ts = jnp.arange(T, dtype=jnp.int32)
        xs = (X, Y, ts, jnp.full((T,), delta, jnp.float32),
              jnp.full((T,), period, jnp.int32))
        return lax.scan(step, carry, xs)[1]

    return simulate


def run(cfg: dict, protocol: dict, X: np.ndarray, Y: np.ndarray,
        mode: str = "highest") -> Out:
    """The reference's per-round outputs for one stream (T, m, d)."""
    T, m, d = X.shape
    kind = protocol["kind"]
    if kind not in ("dynamic", "periodic") or protocol.get("mini_batch", 1) != 1:
        raise ValueError(f"the SV reference runs dynamic (mini_batch 1) or "
                         f"periodic protocols, not {protocol}")
    sim = _program(m, cfg["budget"], d, float(cfg["gamma"]), float(cfg["eta"]),
                   float(cfg["lam"]), kind, mode)
    outs = sim(jnp.asarray(X), jnp.asarray(Y), protocol.get("delta", 0.0),
               protocol.get("period", 1))
    return Out(*(np.asarray(o) for o in outs))
