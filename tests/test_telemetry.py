"""Tests of the telemetry layer (repro.telemetry, DESIGN.md Sec. 11).

Four contracts:

- **Trace determinism** — a trace is a pure function of the run's
  seeds: identical configuration gives byte-identical Chrome-trace
  JSON (extends test_runtime.py::test_determinism_under_seed to the
  trace layer), and the per-message byte annotations sum to the run's
  ``total_bytes``.
- **Monitor exactness** — the live loss-proportionality monitor adopts
  the driver's cumulative series bitwise (losses) / integer-exactly
  (bytes) for {SV, RFF, linear} x {scan engine, async harness,
  serving engine}.
- **Compile-cache regression** — using the compile counter, a second
  value-equal configuration adds ZERO backend compiles to ``engine.run``
  and ``engine.sweep`` stays at one compile per (substrate, kind)
  group, pinning the frozen/hashable-substrate cache keying of PR 3.
- **Bench reports** — BENCH_*.json round-trips through the schema
  validator and ``tools/bench_compare.py`` passes a self-diff and
  fails an injected regression.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from repro.core import engine
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rff import RFFSpec
from repro.core.rkhs import KernelSpec
from repro.data.streams import susy_stream
from repro.runtime import (AsyncProtocolConfig, SystemConfig,
                           run_async_simulation)
from repro.serving import serve_stream
from repro.telemetry import (CompileCounter, CriterionMonitor, Tracer,
                             monitor_result, monitor_sweep, unit_bytes_of)
from repro.telemetry.trace import PID_NETWORK, PID_SERVING, TICKS_PER_UNIT

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)                      # for benchmarks.common

from benchmarks.common import (BenchReport, Row, load_report,  # noqa: E402
                               validate_report)

D = 8
T, M = 150, 4
KCFG = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                     budget=32, kernel=KernelSpec("gaussian", gamma=0.3),
                     dim=D)
RSPEC = RFFSpec(dim=D, num_features=64, gamma=0.3, seed=0)
LCFG = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1, lam=0.001,
                     dim=D)
PCFG = ProtocolConfig(kind="dynamic", delta=2.0)
ACFG_IDEAL = AsyncProtocolConfig(kind="dynamic", delta=2.0, alpha=1.0,
                                 staleness="constant")
X, Y = susy_stream(T=T, m=M, d=D, seed=0)

# the noisy-network configuration of test_runtime's determinism test
NOISY = dict(
    acfg=AsyncProtocolConfig(kind="dynamic", delta=2.0, alpha=0.6,
                             staleness="poly", agg_window=0.5),
    sys_cfg=SystemConfig(seed=3, compute_jitter=0.3, straggler_frac=0.25,
                         base_latency=0.4, latency_jitter=0.5,
                         bandwidth=1e5, drop_prob=0.05))


def _noisy_trace(seed: int = 3) -> tuple:
    cfg = NOISY["sys_cfg"]
    sc = SystemConfig(**{**cfg.__dict__, "seed": seed})
    tr = Tracer()
    res = run_async_simulation(KCFG, NOISY["acfg"], X, Y, sys_cfg=sc,
                               tracer=tr)
    return tr, res


def _load_bench_compare():
    path = os.path.join(ROOT, "tools", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Trace format and determinism
# ---------------------------------------------------------------------------


def test_trace_json_is_perfetto_loadable_shape():
    tr, _ = _noisy_trace()
    doc = json.loads(tr.to_json())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "C", "i", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
        if ev["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values())
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # named tracks: process metadata for every pid that has events
    pids_used = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    pids_named = {e["pid"] for e in doc["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert pids_used <= pids_named
    # learner rounds land as spans at the simulated-time scale
    rounds = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"] == "round"]
    assert len(rounds) == T * M
    assert max(e["ts"] for e in rounds) > TICKS_PER_UNIT


def test_trace_byte_annotations_sum_to_total_bytes():
    tr, res = _noisy_trace()
    # bytes leave the sender whether or not the network drops the
    # message, so delivered spans plus drop instants cover the ledger
    msg = [e for e in tr.events
           if e["ph"] == "X" and e["name"].startswith("msg/")]
    drop = [e for e in tr.events
            if e["ph"] == "i" and e["name"].startswith("drop/")]
    assert res.num_dropped > 0 and len(drop) == res.num_dropped
    total = sum(e["args"]["nbytes"] for e in msg + drop)
    assert total == res.total_bytes
    assert all(e["pid"] == PID_NETWORK for e in msg + drop)


def test_trace_byte_identical_under_seed():
    t1, r1 = _noisy_trace()
    t2, r2 = _noisy_trace()
    assert r1.total_bytes == r2.total_bytes
    assert t1.to_json() == t2.to_json()       # byte-identical export
    t3, _ = _noisy_trace(seed=4)
    assert t3.to_json() != t1.to_json()       # the seed actually matters


def test_serving_trace_request_lifecycle():
    tr = Tracer()
    res = serve_stream(KCFG, PCFG, X, Y, queries_per_round=2.0, tracer=tr)
    by = {}
    for e in tr.events:
        by.setdefault((e["ph"], e["name"]), []).append(e)
    enq = by[("i", "enqueue")]
    req = by[("X", "request")]
    assert len(enq) == res.num_requests
    assert len(req) == res.num_requests       # every request answered
    assert {e["args"]["uid"] for e in enq} == {e["args"]["uid"] for e in req}
    assert all(e["dur"] >= 0 and e["pid"] == PID_SERVING for e in req)
    rounds = by[("i", "round")]
    assert len(rounds) == res.rounds
    syncs = by.get(("X", "sync/transfer"), [])
    assert len(syncs) == res.num_syncs > 0
    assert sum(e["args"]["nbytes"] for e in syncs) == res.total_bytes
    buckets = [e for (ph, name), evs in by.items() if ph == "X"
               and name.startswith("predict/bucket") for e in evs]
    assert buckets
    assert all(1 <= e["args"]["filled"] <= e["args"]["bucket"]
               for e in buckets)
    assert ("C", "serve/queue_depth") in by
    assert ("C", "serve/bucket_occupancy") in by


# ---------------------------------------------------------------------------
# Live loss-proportionality monitor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("learner", [KCFG, RSPEC, LCFG],
                         ids=["sv", "rff", "linear"])
def test_monitor_exact_across_drivers(learner):
    """The monitor's series are the driver's series — bitwise losses,
    integer-exact bytes — for all three substrates and all three
    drivers, and the dynamic protocol satisfies the criterion."""
    res_e = engine.run(learner, PCFG, X, Y)
    res_a = run_async_simulation(learner, ACFG_IDEAL, X, Y,
                                 sys_cfg=SystemConfig())
    res_s = serve_stream(learner, PCFG, X, Y, queries_per_round=1.0).sim
    for res in (res_e, res_a, res_s):
        mon = monitor_result(res, learner, M)
        s = mon.series()
        assert s.cumulative_bytes.dtype == np.int64
        np.testing.assert_array_equal(s.cumulative_bytes,
                                      res.cumulative_bytes)
        np.testing.assert_array_equal(s.cumulative_loss,
                                      res.cumulative_loss)
        assert len(s) == T and s.ok and mon.ok
    # the three drivers' ledgers agree, so the monitors do too
    np.testing.assert_array_equal(res_e.cumulative_bytes,
                                  res_a.cumulative_bytes)
    np.testing.assert_array_equal(res_e.cumulative_bytes,
                                  res_s.cumulative_bytes)
    np.testing.assert_array_equal(res_e.cumulative_loss,
                                  res_s.cumulative_loss)  # bitwise
    np.testing.assert_allclose(res_e.cumulative_loss,
                               res_a.cumulative_loss, rtol=1e-5)


def test_monitor_unit_bytes_topologies():
    # coordinator SV worst case: full-budget novel uploads + union
    # downloads; allreduce: the substrate's fixed ring total
    ub = unit_bytes_of(KCFG, M)
    bx, ba = D * 4 + 4, 4 + 4
    tau = KCFG.budget
    assert ub == (M * tau * (ba + bx)
                  + M * M * tau * ba + M * (M - 1) * tau * bx)
    assert unit_bytes_of(LCFG, M) == 2 * M * (D + 1) * 4   # weights + bias
    assert unit_bytes_of(KCFG, M, "allreduce") > 0
    with pytest.raises(ValueError):
        unit_bytes_of(KCFG, M, "ring")


def test_monitor_flags_disproportionate_communication():
    mon = CriterionMonitor(m=2, unit_bytes=100, slack=1.0, loss_floor=1.0)
    assert mon.observe(0.0, 150)        # 150 <= 1 * 2 * 100 * 1
    assert not mon.observe(0.0, 500)    # 650 > 200: loss never grew
    assert mon.observe(10.0, 0)         # bound catches up with the loss
    assert mon.violation_round == 1 and not mon.ok
    s = mon.series()
    assert s.ratio[1] > 1.0 and s.ratio[0] <= 1.0
    assert not s.ok
    tr = Tracer()
    mon.emit(tr)
    names = [e["name"] for e in tr.events]
    assert names.count("criterion/bytes") == mon.rounds
    assert names.count("criterion/loss") == mon.rounds
    assert names.count("criterion/violation") == 1


def test_monitor_sweep_matches_per_config_ledgers():
    grid = [ProtocolConfig(kind="dynamic", delta=d) for d in (0.5, 2.0)]
    sw = engine.sweep(KCFG, grid, X, Y)
    mons = monitor_sweep(sw, KCFG, M)
    assert len(mons) == len(grid)
    for i, mon in enumerate(mons):
        np.testing.assert_array_equal(mon.series().cumulative_bytes,
                                      sw[i].cumulative_bytes)
        assert mon.ok


# ---------------------------------------------------------------------------
# Compile counters: the engine's cache-keying contract
# ---------------------------------------------------------------------------

# distinctive values so these tests key fresh engine._jitted entries no
# other test warmed (the lru_cache is process-wide)
KCFG_DISTINCT = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.47,
                              lam=0.013, budget=48,
                              kernel=KernelSpec("gaussian", gamma=0.317),
                              dim=D)
X2, Y2 = susy_stream(T=60, m=M, d=D, seed=2)


def test_engine_run_reuses_compile_across_equal_configs():
    engine.run(KCFG_DISTINCT, ProtocolConfig(kind="dynamic", delta=0.7),
               X2, Y2)                        # warm: compiles the scan
    # a NEW value-equal config and different protocol parameters must
    # be a pure cache hit: frozen substrates key on value, and
    # delta / period are runtime params, not trace constants
    cfg_b = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.47,
                          lam=0.013, budget=48,
                          kernel=KernelSpec("gaussian", gamma=0.317),
                          dim=D)
    assert cfg_b == KCFG_DISTINCT and cfg_b is not KCFG_DISTINCT
    with CompileCounter() as c:
        engine.run(cfg_b, ProtocolConfig(kind="dynamic", delta=1.9), X2, Y2)
    assert c.compiles == 0


def test_engine_sweep_one_compile_per_substrate_kind_group():
    dyn = [ProtocolConfig(kind="dynamic", delta=d) for d in (0.41, 1.7)]
    engine.sweep(KCFG_DISTINCT, dyn, X2, Y2)  # warm the dynamic@2 group
    with CompileCounter() as c1:
        engine.sweep(KCFG_DISTINCT,
                     [ProtocolConfig(kind="dynamic", delta=d)
                      for d in (0.93, 2.9)], X2, Y2)
    assert c1.compiles == 0                   # same group, new deltas
    # warm the size-1 param-stacking eager ops (shapes are substrate-
    # independent) on a DIFFERENT substrate, so the only thing left to
    # compile below is the new (substrate, kind) group executable
    lcfg_distinct = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.23,
                                  lam=0.0017, dim=D)
    engine.sweep(lcfg_distinct, [ProtocolConfig(kind="periodic", period=11)],
                 X2, Y2)
    with CompileCounter() as c2:
        engine.sweep(KCFG_DISTINCT,
                     dyn + [ProtocolConfig(kind="periodic", period=7)],
                     X2, Y2)
    assert c2.compiles == 1                   # exactly the new group


# ---------------------------------------------------------------------------
# Bench reports and the comparator
# ---------------------------------------------------------------------------


def _report(suite="demo", us=100.0, claim=True):
    rows = [
        Row(f"{suite}/hot_loop", us, "rounds_per_sec=10.0"),
        Row(f"{suite}/claims", 0.0,
            f"parity={claim};speedup=3.1x"),
    ]
    return BenchReport(suite, rows, wall_seconds=0.5)


def test_bench_report_schema_roundtrip(tmp_path):
    rep = _report()
    doc = rep.to_dict()
    assert validate_report(doc) == []
    assert doc["claims"] == {"demo/claims/parity": True}
    path = rep.save(str(tmp_path))
    assert os.path.basename(path) == "BENCH_demo.json"
    assert load_report(path)["suite"] == "demo"
    # the validator actually rejects malformed documents
    assert validate_report({"suite": "x"})
    bad = rep.to_dict()
    bad["rows"][0]["us_per_call"] = "fast"
    assert any("us_per_call" in p for p in validate_report(bad))
    bad2 = rep.to_dict()
    bad2["claims"]["demo/claims/parity"] = "yes"
    assert any("claim" in p for p in validate_report(bad2))


def test_bench_compare_self_diff_and_regressions(tmp_path):
    bc = _load_bench_compare()
    base, cand = tmp_path / "base", tmp_path / "cand"
    _report().save(str(base))
    _report().save(str(cand))
    assert bc.main([str(base), str(cand)]) == 0          # self-diff

    bad = tmp_path / "bad"
    _report(us=300.0, claim=False).save(str(bad))        # 3x + claim flip
    assert bc.main([str(base), str(bad)]) == 1
    regs = bc.compare(bc.load_dir(str(base)), bc.load_dir(str(bad)))
    assert any("demo/hot_loop" in r for r in regs)
    assert any("parity" in r for r in regs)
    # a generous per-metric override waives the timing gate
    regs2 = bc.compare(bc.load_dir(str(base)), bc.load_dir(str(bad)),
                       overrides=[("demo/*", 10.0)])
    assert not any(r.startswith("[timing]") for r in regs2)
    # sub-threshold rows are not flagged
    ok = tmp_path / "ok"
    _report(us=120.0).save(str(ok))
    assert bc.main([str(base), str(ok)]) == 0
    # a vanished row is a coverage regression
    missing = tmp_path / "missing"
    rep = _report()
    rep.rows = rep.rows[1:]
    rep.save(str(missing))
    assert bc.main([str(base), str(missing)]) == 1


def _bytes_report(nbytes: int, gram: int):
    rows = [Row("demo/ledger", 500.0,
                f"bytes={nbytes};hbm_gram_bytes={gram};ratio=0.33")]
    return BenchReport("demo", rows, wall_seconds=0.1)


def test_bench_compare_byte_metrics_exact(tmp_path):
    # byte ledgers are integer-exact under seed (DESIGN.md Sec. 7):
    # any drift in a *bytes* derived metric is a regression at exact
    # integer equality, regardless of the timing threshold.
    bc = _load_bench_compare()
    base, same, drift = tmp_path / "b", tmp_path / "s", tmp_path / "d"
    _bytes_report(150336, 262144).save(str(base))
    _bytes_report(150336, 262144).save(str(same))
    _bytes_report(150336, 262148).save(str(drift))     # 4-byte drift

    assert bc.byte_metrics({"derived": "bytes=12;x=1.5"}) == {"bytes": 12}
    assert bc.byte_metrics({"derived": "ratio=0.8"}) == {}

    assert bc.main([str(base), str(same), "--threshold", "25"]) == 0
    assert bc.main([str(base), str(drift), "--threshold", "25"]) == 1
    regs = bc.compare(bc.load_dir(str(base)), bc.load_dir(str(drift)),
                      threshold=25.0)
    assert any(r.startswith("[bytes]") and "hbm_gram_bytes" in r
               for r in regs)
    # cross-version comparisons can downgrade the gate to a warning
    assert bc.main([str(base), str(drift), "--threshold", "25",
                    "--allow-bytes-drift"]) == 0
