"""Readings that the correctness limits are set from, on the chip.

  python3 chipbench/tests/readings.py --workload <cell> --seeds 12 --control 3

For each of ``--seeds`` seeds, the system under test runs every stream
of the cell's pool once and is compared with the reference, the
numbers combined over the pool as a run combines them; for the first
``--control`` seeds the control, the reference computed at the next
precision below (``high``, three bfloat16 passes), is compared with
the reference the same way.  Prints one JSON line per reading: the
lower reading of a number is the largest the system gives, the upper
the smallest the control gives.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import compare, harness, streams  # noqa: E402


def control_series(ref) -> dict:
    """A reference run as the system's per-round series (sums in float32
    as the system's host code makes them)."""
    return {"loss": compare.summed(ref.loss), "err": compare.summed(ref.err),
            "bytes": ref.nbytes, "sync": ref.sync, "eps": ref.eps}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=5000)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    cfg, protocol = cell.cfg, cell.traffic["protocol"]
    harness.enable_compile_cache()
    harness.devices(cfg["chips"], harness.load_peaks())
    system = harness.system_module(cfg).build(cfg, protocol)
    ref = harness.reference_module(cfg)
    for n in range(args.seeds):
        seed = args.first_seed + n
        pool = streams.pool(cfg, cell.traffic, seed)
        sys_rows, ctl_rows, syncs, t_sys, t_ref = [], [], 0, 0.0, 0.0
        for X, Y in pool:
            t0 = time.perf_counter()
            prog = system.series(system.run(X, Y))
            t1 = time.perf_counter()
            hi = ref.run(cfg, protocol, X, Y, "highest")
            t_sys, t_ref = t_sys + t1 - t0, t_ref + time.perf_counter() - t1
            syncs += int(prog["sync"].sum())
            sys_rows.append(compare.compare(prog, hi, cfg["ambiguity"]))
            if n < args.control:
                lo = ref.run(cfg, protocol, X, Y, "high")
                ctl_rows.append(compare.compare(control_series(lo), hi,
                                                cfg["ambiguity"]))
        for who, rows in (("system", sys_rows), ("control", ctl_rows)):
            if rows:
                print(json.dumps({
                    "seed": seed, "who": who, "syncs": syncs, "system_s": t_sys,
                    "reference_s": t_ref, **compare.combine(rows),
                    "compared_rounds": [r["compared_rounds"] for r in rows]}),
                    flush=True)

if __name__ == "__main__":
    main()
