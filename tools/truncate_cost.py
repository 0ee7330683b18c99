#!/usr/bin/env python3
"""Device time of one sync truncation against u, the distinct dropped ids.

Builds an m tau slot expansion whose tau largest coefficients sit on
distinct points and whose other slots repeat u points, truncates it to
tau, and prints one JSON line per u with the microseconds a call takes:

  truncate      ``compression.truncate`` (tile loop or one Gram by u)
  tile_loop     the (512, 512) tile loop at every u
  merged_gram   one (n, n) Gram over the merged points at every u
  dense         the slot-by-slot (n, n) Gram, as before the merge

and each variant's epsilon. Run on the device to be measured:

  python3 tools/truncate_cost.py [--slots 16384] [--tau 512] [--reps 30]
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import compression as C  # noqa: E402
from repro.core.rkhs import KernelSpec, SVModel, gram, quadform  # noqa: E402

SPEC = KernelSpec(kind="gaussian", gamma=0.3)
D = 8


def expansion(n: int, tau: int, u: int, key) -> SVModel:
    """tau kept slots on distinct points, n - tau dropped slots over u."""
    kp, ka = jax.random.split(key)
    pts = jax.random.normal(kp, (tau + u, D), jnp.float32)
    s = jnp.arange(n)
    ids = jnp.where(s < tau, s, tau + (s - tau) % max(u, 1))
    if u == 0:
        ids = jnp.where(s < tau, s, -1)
    a = jax.random.uniform(ka, (n,), jnp.float32, 0.01, 0.1)
    a = jnp.where(ids < 0, 0.0, jnp.where(s < tau, 1.0 + a, a))
    sv = jnp.where((ids >= 0)[:, None], pts[jnp.maximum(ids, 0)], 0.0)
    return SVModel(sv, a, ids.astype(jnp.int32))


def variants(n: int, tau: int) -> dict:
    def merged(tb):
        def fn(f):
            keep = C._top_tau_mask(f, tau)
            drop = C.merge_dropped(f, C._dropped_beta(f, keep))
            return C._pack_to_budget(f, keep, tau), jnp.sqrt(
                jnp.maximum(C._tiled_norm_sq(SPEC, f.sv, drop, tb), 0.0))
        return jax.jit(fn)

    @jax.jit
    def dense(f):
        keep = C._top_tau_mask(f, tau)
        beta = C._dropped_beta(f, keep)
        return C._pack_to_budget(f, keep, tau), jnp.sqrt(jnp.maximum(
            quadform(gram(SPEC, f.sv, f.sv), beta, beta), 0.0))

    return {"truncate": jax.jit(lambda f: C.truncate(SPEC, f, tau)),
            "tile_loop": merged(min(C._TILE, n)), "merged_gram": merged(n),
            "dense": dense}


def clock(fn, f, reps: int) -> tuple:
    """Median over 5 of the mean time of ``reps`` calls back to back."""
    out = jax.block_until_ready(fn(f))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(f)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / reps * 1e6)
    return statistics.median(times), float(out[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=16384)
    ap.add_argument("--tau", type=int, default=512)
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)
    n, tau = args.slots, args.tau
    fns = variants(n, tau)
    print(jax.devices()[0].device_kind, flush=True)
    for u in (0, 352, 512, 1024, 2048, 4096, 6144, 8192, 12288, 12800, n - tau):
        if u > n - tau:
            continue
        f = expansion(n, tau, u, jax.random.PRNGKey(u))
        row = {"u": u, "counted": int(C.distinct_dropped(f, tau))}
        for name, fn in fns.items():
            t, eps = clock(fn, f, args.reps)
            row[name + "_us"], row[name + "_eps"] = round(t, 1), eps
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
