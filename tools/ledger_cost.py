#!/usr/bin/env python3
"""Device time of one sync's byte ledger, at m learners of tau slots.

Fills the coordinator cache (m tau slots) with a first sync of m tau
distinct ids, then times the next sync's Sec. 3 bytes against that warm
cache, for rows whose ids are in the cache at a given share:

  ledger        ``accounting.device_sync_bytes_kernel`` as it stands
  searchsorted  the same bytes with membership as a per-row binary
                search of each sorted row into the cache
                (``rkhs.count_members`` vmapped over the m rows)

and prints one JSON line per share with the microseconds a call takes
and each variant's bytes, which must agree. Run on the device to be
measured:

  python3 tools/ledger_cost.py [--learners 32] [--tau 512] [--reps 30]
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
import numpy as np  # noqa: E402

from repro.core import accounting, rkhs  # noqa: E402

BM = accounting.ByteModel(dim=8)


def searchsorted_bytes(ids, ledger):
    """The Sec. 3 bytes with a per-row probe of the cache."""
    m = ids.shape[0]
    uniq, n = jax.vmap(rkhs.sorted_unique)(ids)
    union, u = rkhs.sorted_unique(uniq)
    in_known = jax.vmap(lambda q: rkhs.count_members(q, ledger.known))(uniq)
    n_total = jnp.sum(n)
    total = (n_total * BM.B_alpha + jnp.sum(n - in_known) * BM.B_x
             + m * u * BM.B_alpha + (m * u - n_total) * BM.B_x)
    return total, accounting.DeviceLedger(known=union)


VARIANTS = {
    "ledger": lambda ids, led: accounting.device_sync_bytes_kernel(
        BM, ids, led),
    "searchsorted": searchsorted_bytes,
}


def sync_ids(m: int, tau: int, share: float, seed: int) -> np.ndarray:
    """m rows of tau distinct ids, a ``share`` of each row drawn from
    the warm cache's ids [0, m tau), the rest fresh."""
    rng = np.random.default_rng(seed)
    cached = int(round(share * tau))
    rows = [np.concatenate([
        rng.choice(m * tau, cached, replace=False),
        m * tau + i * tau + np.arange(tau - cached)]) for i in range(m)]
    return rng.permuted(np.stack(rows), axis=1).astype(np.int32)


def clock(fn, args, reps: int) -> tuple:
    """Median over 5 of the mean time of ``reps`` calls back to back."""
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / reps * 1e6)
    return statistics.median(times), int(out[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--learners", type=int, default=32)
    ap.add_argument("--tau", type=int, default=512)
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)
    m, tau = args.learners, args.tau
    fns = {name: jax.jit(fn) for name, fn in VARIANTS.items()}
    print(jax.devices()[0].device_kind, flush=True)
    first = jnp.arange(m * tau, dtype=jnp.int32).reshape(m, tau)
    _, warm = fns["ledger"](first, accounting.device_ledger_init(m * tau))
    for share in (0.0, 0.5, 1.0):
        ids = jnp.asarray(sync_ids(m, tau, share, seed=int(share * 10)))
        row = {"m": m, "tau": tau, "cached_share": share}
        for name, fn in fns.items():
            t, b = clock(fn, (ids, warm), args.reps)
            row[name + "_us"], row[name + "_bytes"] = round(t, 1), b
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
