"""The benchmark harness: one run of one cell.

Everything a cell needs is found by name.  ``BENCHMARK.json`` at the
repository root names the cell's configuration (its ``file``), its
traffic mix (``chipbench/traffic/<traffic>.json``) and its metrics.  A
configuration's ``kind`` names the system under test
(``chipbench/systems/<kind>.py``, exposing ``build(cfg, protocol)``)
and its plain reference (``chipbench/references/<kind>.py``, exposing
``run(cfg, protocol, X, Y, mode)``).  A per-layer metric is read by
``chipbench/metrics/<name>.py``, exposing ``read(reading)`` that returns
a number or None.

A run: set-up (streams from the seed, the system built, one warm-up
experiment that compiles or loads the program), then the window, a
closed loop of whole experiments back to back over the stream pool,
then the comparison with the reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

#: the traced sub-window of a --trace 1 run: at least one experiment
#: on each stream of the pool, and at least ``TRACE_SECONDS`` (capped
#: by --seconds)
TRACE_SECONDS = 1.0


class NoChip(Exception):
    """The run is on no accelerator, too few chips, or an unknown one."""


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s BENCHMARK.json with its files read."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, cfg=cfg, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def system_module(cfg: dict):
    return importlib.import_module(f"chipbench.systems.{cfg['kind']}")


def reference_module(cfg: dict):
    return importlib.import_module(f"chipbench.references.{cfg['kind']}")


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """``read`` of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(root, "chipbench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(root: str = ROOT) -> dict:
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def devices(chips: int, peaks: dict, require_chip: bool = True):
    """The first ``chips`` devices and their description; NoChip unless
    they are TPUs of a kind ``peaks.json`` lists."""
    import jax

    devs = jax.devices()
    if require_chip:
        if jax.default_backend() != "tpu":
            raise NoChip(f"JAX's backend is {jax.default_backend()!r}, not a TPU")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
        if devs[0].device_kind not in peaks:
            raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                         "chipbench/peaks.json")
    return devs[:chips], {"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``; every program
    is kept, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


def window(system, pool, seconds: float, min_experiments: int = 1,
           annotate: bool = False):
    """Whole experiments back to back over the pool until ``seconds`` have
    passed (and at least ``min_experiments`` ran).  Returns the
    (pool index, result) pairs and the seconds from the window's start
    to the end of the last experiment."""
    import jax

    def span(name):
        if annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    results = []
    t0 = time.perf_counter()
    while True:
        i = len(results) % len(pool)
        with span("chipbench.experiment"):
            res = system.run(*pool[i])
        with span("chipbench.between"):
            results.append((i, res))
            elapsed = time.perf_counter() - t0
        if elapsed >= seconds and len(results) >= min_experiments:
            return results, elapsed


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------


def check(system, results, pool, cell: Cell, ref_run: Callable,
          log: Callable = lambda *a: None):
    """Compare every experiment of the window with the reference: each
    stream of the pool that ran is run once through the reference, and
    every repeat of a stream has to equal its first run bitwise.
    Returns (numbers, limits, failed experiment count)."""
    from chipbench import compare

    cfg, protocol = cell.cfg, cell.traffic["protocol"]
    first: Dict[int, dict] = {}
    bad_repeat = []
    for n, (i, res) in enumerate(results):
        s = system.series(res)
        if i not in first:
            first[i] = s
        elif not compare.same(first[i], s):
            bad_repeat.append(n)
    per_stream = {}
    for i in sorted(first):
        t0 = time.perf_counter()
        ref = ref_run(cfg, protocol, *pool[i])
        per_stream[i] = compare.compare(first[i], ref, cfg["ambiguity"])
        log(f"stream {i}: reference {time.perf_counter() - t0:.3f} s, "
            f"{per_stream[i]}")
    limits = cfg["limits"]
    numbers = compare.combine(list(per_stream.values()))
    numbers["repeat_mismatch"] = len(bad_repeat)
    # a stream is wrong by its own numbers; the least compared rounds
    # hold for the pool's streams together
    own = {k: v for k, v in limits.items() if not k.startswith("min_")}
    wrong = {i for i, p in per_stream.items() if not compare.within(
        dict(p, repeat_mismatch=0), own)}
    failed = sum(1 for n, (i, _) in enumerate(results)
                 if i in wrong or n in bad_repeat)
    log("compared rounds per stream: "
        f"{[p['compared_rounds'] for p in per_stream.values()]} of {cfg['rounds']}")
    return numbers, dict(limits), failed


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, require_chip: bool = True,
        log: Callable = lambda *a: None) -> dict:
    """One run of one cell; returns the result line's object."""
    from chipbench import compare, streams
    from chipbench.compiles import CompileCounter

    cell = resolve(workload, root)
    cfg = cell.cfg
    peaks = load_peaks(root)
    devs, device = devices(cfg["chips"], peaks, require_chip)
    log(f"device {device}; compile cache {enable_compile_cache()}; "
        f"{time.perf_counter() - t_start:.3f} s from the start")

    pool = streams.pool(cfg, cell.traffic, seed)
    system = system_module(cfg).build(cfg, cell.traffic["protocol"])
    with CompileCounter() as warm:
        system.run(*pool[0])
    log(f"warm-up: {warm.compiles} compiles, {warm.seconds:.3f} s compiling; "
        f"{time.perf_counter() - t_start:.3f} s from the start")
    hlo = system.hlo_text(*pool[0]) if trace else ""
    setup_s = time.perf_counter() - t_start

    tracedir = None
    with CompileCounter() as inside:
        if trace:
            import jax

            tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")
            with jax.profiler.trace(tracedir):
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    results, elapsed = window(
                        system, pool, min(seconds, TRACE_SECONDS),
                        len(pool), annotate=True)
        else:
            results, elapsed = window(system, pool, seconds)
    log(f"window: {len(results)} experiments in {elapsed:.6f} s, "
        f"{inside.compiles} compiles inside it")
    device["memory_peak_bytes"] = memory_peak(devs)

    rounds = len(results) * cfg["rounds"]
    out = {"correct": None, "attempted": len(results), "failed": None,
           "metrics": {}, "device": device}
    if trace:
        reading, breakdown = read_trace(tracedir, hlo, cell, results, system,
                                        peaks.get(device["kind"]), log)
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(reading)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = breakdown
    else:
        values = {"learner_rounds_per_s": rounds * cfg["learners"] / elapsed,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    numbers, limits, failed = check(system, results, pool, cell,
                                    reference_module(cfg).run, log)
    numbers["window_compiles"] = inside.compiles
    limits["window_compiles"] = 0
    out["failed"] = failed
    out["correct"] = failed == 0 and compare.within(numbers, limits)
    out["checks"] = {k: {"value": compare.value_of(k, numbers), "limit": v}
                     for k, v in limits.items()}
    return out


def read_trace(tracedir, hlo, cell, results, system, peak, log):
    from chipbench import trace as tr

    t0 = time.perf_counter()
    kernels = tr.kernel_names(hlo)
    if tr.custom_calls(hlo) and not kernels:
        raise RuntimeError("the compiled program's tpu_custom_calls name no kernel "
                           "function: chipbench/trace.py cannot map them")
    try:
        path = tr.find_xplane(tracedir)
        size = os.path.getsize(path)
        summary = tr.reduce(tr.load(path), kernels, cell.cfg["chips"])
    finally:
        tr.remove(tracedir)
    if kernels and not any(d.kernels for d in summary.devices):
        raise RuntimeError(f"no device operation of the trace is one of the "
                           f"program's kernel launches {sorted(kernels)}")
    syncs = sum(int(system.series(r)["sync"].sum()) for _, r in results)
    reading = tr.Reading(
        cfg=cell.cfg, traffic=cell.traffic, peak=peak, summary=summary,
        rounds=len(results) * cell.cfg["rounds"], syncs=syncs)
    log(f"trace of {size} bytes read in {time.perf_counter() - t0:.3f} s")
    return reading, summary.breakdown()
