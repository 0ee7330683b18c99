"""Run one benchmark cell once.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and, last, each number the correctness comparison
checked beside its limit on standard error; the last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
``checks`` last).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.  Exits
non-zero, and prints no result, off a TPU, with fewer chips than the
cell needs, on a device kind ``chipbench/peaks.json`` does not list,
or when the system under test cannot be imported.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"chipbench: {e}")
        return 1
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in out["checks"].items():
        side = "at least" if name.startswith("min_") else "limit"
        log(f"check {name}: {c['value']!r} ({side} {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
