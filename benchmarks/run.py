"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--quick] [--only NAME] [--json-dir DIR]

Output: ``name,us_per_call,derived`` CSV rows (plus a summary).  With
``--json-dir`` each suite additionally writes a machine-readable
``BENCH_<suite>.json`` report (env fingerprint, rows, gated claims —
see ``common.BenchReport``) that ``tools/bench_compare.py`` can diff
against a baseline directory.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.cache import enable_compile_cache

from . import (bench_adaptive, bench_async, bench_bounds, bench_comm_time,
               bench_compression, bench_engine, bench_kernels,
               bench_lm_protocol, bench_population, bench_rff,
               bench_roofline, bench_serve, bench_stock, bench_tradeoff)
from .common import BenchReport, print_rows

SUITES = {
    "tradeoff": bench_tradeoff,        # Fig. 1(a)
    "comm_time": bench_comm_time,      # Fig. 1(b)
    "engine": bench_engine,            # loop vs scan vs sweep (DESIGN.md 7)
    "async": bench_async,              # sync-vs-async runtime (DESIGN.md 6)
    "stock": bench_stock,              # Fig. 2
    "bounds": bench_bounds,            # Thm.4 / Prop.5 / Prop.6 / Thm.7
    "compression": bench_compression,  # Sec. 3/4 ablation
    "rff": bench_rff,                  # Sec. 4 future-work
    "serve": bench_serve,              # online serving (DESIGN.md 10)
    "adaptive": bench_adaptive,        # Sec. 4 open problem (beyond paper)
    "lm_protocol": bench_lm_protocol,  # the technique at LM scale (measured)
    "kernels": bench_kernels,          # Pallas hot-spots
    "roofline": bench_roofline,        # §Roofline summary
    "population": bench_population,    # 10^5-10^6 learners (DESIGN.md 15)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes for CI")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="also write BENCH_<suite>.json reports here")
    args = ap.parse_args()
    enable_compile_cache()

    names = [args.only] if args.only else list(SUITES)
    all_rows, failures = [], []
    for name in names:
        t0 = time.time()
        try:
            rows = SUITES[name].run(quick=args.quick)
            all_rows.extend(rows)
            wall = time.time() - t0
            print(f"# {name}: {len(rows)} rows in {wall:.1f}s",
                  file=sys.stderr)
            if args.json_dir:
                path = BenchReport(name, rows, wall_seconds=wall).save(
                    args.json_dir)
                print(f"# {name}: wrote {path}", file=sys.stderr)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    print_rows(all_rows)
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
