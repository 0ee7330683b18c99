"""Record one cell's window under the profiler, on the chip, and say where
its device and host time went by the program's own names.

  python3 chipbench/tests/record_trace.py --workload <cell> [--rounds T]
      [--experiments N] [--seed S] [--out PREFIX]

Runs like a ``--trace 1`` run of ``chipbench/run.py`` (set-up, the
``chipbench.window`` span, ``harness.window`` with its spans), with the
cell's configuration cut to ``--rounds`` and the window to
``--experiments`` whole experiments.  Prints one JSON line: each
scope's device time on the busiest chip (chipbench/scopes.py) and the
chips' idle time inside each ``repro.engine.*`` host span.  With
``--out`` it keeps the trace as ``PREFIX.xplane.pb`` and what the tests
need beside it as ``PREFIX.json`` (the kernel and scope maps of the
compiled program).
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness, scopes, streams, trace  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int)
    ap.add_argument("--experiments", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    cell = harness.resolve(args.workload)
    cfg = dict(cell.cfg, rounds=args.rounds or cell.cfg["rounds"])
    harness.enable_compile_cache()
    _, device = harness.devices(cfg["chips"], harness.load_peaks())
    pool = streams.pool(cfg, cell.traffic, args.seed)
    system = harness.system_module(cfg).build(cfg, cell.traffic["protocol"])
    system.run(*pool[0])
    hlo = system.hlo_text(*pool[0])
    kernels = trace.kernel_names(hlo)
    roots = scopes.scope_roots(hlo)

    tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tracedir):
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                results, elapsed = harness.window(system, pool, 0.0, args.experiments,
                                                  annotate=True)
        path = trace.find_xplane(tracedir)
        data = trace.load(path)
        summary = trace.reduce(data, kernels, cfg["chips"])
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            shutil.copy(path, args.out + ".xplane.pb")
    finally:
        trace.remove(tracedir)

    syncs = sum(int(system.series(r)["sync"].sum()) for _, r in results)
    busiest = max(summary.devices, key=lambda d: d.busy_ns)
    scoped = scopes.scope_ns(busiest.ops_ns, roots)
    idle = scopes.idle_in_spans(summary, scopes.host_spans(data))
    n = len(results)
    out = {"workload": args.workload, "rounds": cfg["rounds"], "experiments": n,
           "syncs": syncs, "window_s": summary.window_ns / 1e9, "elapsed_s": elapsed,
           "busy_s": [d.busy_ns / 1e9 for d in summary.devices],
           "scope_s": {k: v / 1e9 for k, v in scoped.items()},
           "scope_share_of_busy": sum(scoped.values()) / busiest.busy_ns,
           "idle_per_experiment_us": {k: v / 1e3 / n for k, v in idle.items()},
           "idle_per_experiment_us_total": (summary.window_ns - sum(
               d.busy_ns for d in summary.devices) / len(summary.devices)) / 1e3 / n,
           "device": device}
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out + ".json", "w") as f:
            json.dump({"kernels": kernels, "scope_names": scopes.scope_names(hlo),
                       "scope_roots": roots, "experiments": n, "rounds": cfg["rounds"],
                       "learners": cfg["learners"], "syncs": syncs,
                       "device_kind": device["kind"]}, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
