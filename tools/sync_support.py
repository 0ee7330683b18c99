#!/usr/bin/env python3
"""How many distinct points each SV sync drops: the u of ``truncate``.

Replays a benchmark cell's configuration and traffic through
``engine.run`` on the CPU and records, at every sync, the number u of
distinct support-vector ids that the truncation to the sync budget drops
(``compression.distinct_dropped`` of the Prop. 2 average). Prints the
distribution of u over the syncs, and of u / (m tau), the share of the
averaged slots that the compression error's Gram still covers.

  JAX_PLATFORMS=cpu python tools/sync_support.py --workload susy-sv512.periodic

The replay runs the ``reference`` backend, the plain jnp path of the
same protocol: Pallas kernels in interpret mode are slow on the CPU.
It holds m tau^2 kernel values a round for the dynamic check, a few
hundred MB at the cells' full size.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, streams  # noqa: E402
from repro.core import compression, engine, rkhs  # noqa: E402


def replay(cell: harness.Cell, seed: int, n_streams: int) -> list:
    """u at each sync of the first ``n_streams`` streams of the cell's
    pool, one list per stream."""
    system = harness.system_module(cell.cfg).build(
        cell.cfg, cell.traffic["protocol"])
    seen: list = []

    # a class of this call's own: the engine caches programs by the
    # substrate, and the callback is part of the program
    @dataclasses.dataclass(frozen=True)
    class Recording(type(system.substrate)):
        def average_stacked(self, models):
            fbar = rkhs.average_stacked(models)
            jax.debug.callback(
                lambda u: seen[-1].append(int(u)),
                compression.distinct_dropped(fbar, self.sync_budget),
                ordered=True)
            return super().average_stacked(models)

    sub = Recording(**{f.name: getattr(system.substrate, f.name)
                       for f in dataclasses.fields(system.substrate)})
    for X, Y in streams.pool(cell.cfg, cell.traffic, seed)[:n_streams]:
        seen.append([])
        res = engine.run(sub, system.pcfg, X, Y, backend="reference")
        # the first call averages the initial models into the reference
        del seen[-1][0]
        if len(seen[-1]) != len(res.sync_rounds):
            raise RuntimeError(f"recorded {len(seen[-1])} syncs, the run "
                               f"reports {len(res.sync_rounds)}")
    return seen


def summary(values: np.ndarray, fmt: str) -> str:
    q = np.percentile(values, [0, 25, 50, 75, 100])
    return " ".join(f"{k} {fmt.format(v)}"
                    for k, v in zip(("min", "q1", "median", "q3", "max"), q))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json with an SV configuration")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=1,
                    help="how many streams of the cell's pool to replay")
    args = ap.parse_args(argv)

    cell = harness.resolve(args.workload)
    if cell.cfg["kind"] != "sv":
        ap.error(f"{args.workload} compresses nothing (kind "
                 f"{cell.cfg['kind']!r})")
    slots = cell.cfg["learners"] * cell.cfg["budget"]
    per_stream = replay(cell, args.seed, args.streams)
    for i, us in enumerate(per_stream):
        print(f"stream {i}: {len(us)} syncs, u {summary(np.asarray(us), '{:.0f}')}")
    u = np.concatenate([np.asarray(us, float) for us in per_stream])
    print(f"{args.workload} seed {args.seed}, {len(per_stream)} streams, "
          f"{len(u)} syncs of m tau = {slots} slots")
    print(f"u:            {summary(u, '{:.0f}')}")
    print(f"u / (m tau):  {summary(u / slots, '{:.4f}')}")
    print(f"u^2 / (m tau)^2, mean: {np.mean((u / slots) ** 2):.3g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
