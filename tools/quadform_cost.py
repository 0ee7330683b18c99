#!/usr/bin/env python3
"""Device time of one dynamic check's three quadform launches.

The check ``SVSubstrate.dist_to_ref`` runs every round of the dynamic
protocol: ||f_i||^2 batched over m learners, <f_i, r> with the
reference r shared, and ||r||^2 once, each a ``_quadform_kernel``
launch.  This times that check at m learners of tau slots in d
features (Gaussian kernel) under each layout:

  tiled       (128, 128) tiles over d padded to 128 lanes, one grid
              step a tile pair (the layout before the re-blocking)
  s<S>        ``kernels/quadform.py``: one step a learner's block pair,
              d unpadded, the cross term as d multiply-adds on the
              vector unit, the Gram walked in S-row strips (``_STRIP``)

and prints one JSON line per d: each variant's microseconds a check
(``--reps`` checks in one jitted loop, median of 5 loops) and its
largest relative gap to the jnp reference (a variant the compiler
refuses prints its error).  The s<S> variants are
``kernels/quadform.py`` itself with ``_STRIP`` set for the run.  Run
on the device to be measured:

  python3 tools/quadform_cost.py [--learners 32] [--tau 512]
                                 [--dims 8 16 32 64] [--reps 200]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.core import rkhs  # noqa: E402
from repro.kernels import ops, quadform  # noqa: E402

GAMMA = 0.3
SPEC = rkhs.KernelSpec(kind="gaussian", gamma=GAMMA)
LANE = 128


def _tiled_quadform(x_ref, y_ref, a_ref, b_ref, o_ref):
    """The (128, 128)-tile body: one Gram tile and its sum per step."""
    cross = jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    xx = jnp.sum(x_ref[...] ** 2, axis=1, keepdims=True)
    yy = jnp.sum(y_ref[...] ** 2, axis=1, keepdims=True).T
    k = jnp.exp(-GAMMA * jnp.maximum(xx + yy - 2.0 * cross, 0.0))

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum((a_ref[...].T * k) * b_ref[...])


@jax.jit
def tiled(X, Y, a, b):
    Xp = ops._pad_to(X, 1, LANE)
    Yp = ops._pad_to(Y, 1, LANE)
    M, d = Xp.shape
    N = Yp.shape[0]
    out = pl.pallas_call(
        _tiled_quadform, grid=(M // LANE, N // LANE),
        in_specs=[pl.BlockSpec((LANE, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((LANE, d), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, LANE), lambda i, j: (0, i)),
                  pl.BlockSpec((1, LANE), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((1, LANE), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        interpret=ops._interpret(),
    )(Xp, Yp, a.reshape(1, M), b.reshape(1, N))
    return out[0, 0]


def reblocked(X, Y, a, b):
    return quadform.quadform_pallas(X, Y, a, b, kind="gaussian", gamma=GAMMA,
                                    interpret=ops._interpret())


@contextlib.contextmanager
def constants(**values):
    """Set ``kernels/quadform.py``'s module constants for one variant."""
    old = {k: getattr(quadform, k) for k in values}
    for k, v in values.items():
        setattr(quadform, k, v)
    jax.clear_caches()
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(quadform, k, v)
        jax.clear_caches()


def check_loop(q, reps: int):
    """``reps`` dynamic checks in one program; the coefficients carry
    a zero times the running sum so no launch is hoisted out of the
    loop."""
    def check(X, A, Y, b):
        rr = q(Y, Y, b, b)
        return jax.vmap(
            lambda x, a: q(x, x, a, a) + rr - 2.0 * q(x, Y, a, b))(X, A)

    @jax.jit
    def run(X, A, Y, b):
        def body(_, carry):
            s, _ = carry
            dists = check(X, A + 0.0 * s, Y, b + 0.0 * s)
            return s + jnp.sum(dists), dists
        return jax.lax.fori_loop(
            0, reps, body, (jnp.zeros((), jnp.float32),
                            jnp.zeros(X.shape[0], jnp.float32)))[1]
    return run


def clock(fn, args, reps: int) -> tuple:
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) / reps * 1e6)
    return statistics.median(times), np.asarray(out)


def operands(m: int, tau: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(m, tau, d)), jnp.float32)
    A = jnp.asarray(rng.normal(size=(m, tau)) * 0.1, jnp.float32)
    Y = jnp.asarray(rng.normal(size=(tau, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(tau,)) * 0.1, jnp.float32)
    return X, A, Y, b


def variants():
    out = {"tiled": (tiled, {})}
    for s in (8, 16, 32, 64, 128):
        out[f"s{s}"] = (reblocked, dict(_STRIP=s))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--learners", type=int, default=32)
    ap.add_argument("--tau", type=int, default=512)
    ap.add_argument("--dims", type=int, nargs="+", default=[8, 16, 32, 64])
    ap.add_argument("--reps", type=int, default=200)
    args = ap.parse_args(argv)
    print(jax.devices()[0].device_kind, flush=True)
    for d in args.dims:
        args_d = operands(args.learners, args.tau, d)
        X, A, Y, b = args_d
        want = np.asarray(jax.vmap(lambda x, a: rkhs.dist_sq(
            SPEC, rkhs.SVModel(x, a, jnp.zeros(args.tau, jnp.int32)),
            rkhs.SVModel(Y, b, jnp.zeros(args.tau, jnp.int32))))(X, A))
        row = {"d": d, "learners": args.learners, "tau": args.tau}
        for name, (q, consts) in variants().items():
            try:
                with constants(**consts):
                    us, got = clock(check_loop(q, args.reps), args_d,
                                    args.reps)
            except Exception as e:  # a variant the compiler refuses
                row[name + "_error"] = str(e).splitlines()[0][:120]
                continue
            row[name + "_us"] = round(us, 2)
            row[name + "_gap"] = float(np.max(np.abs(got - want))
                                       / np.max(np.abs(want)))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
