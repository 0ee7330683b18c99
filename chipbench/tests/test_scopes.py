"""The program's names in a trace (chipbench/scopes.py) against counts
made by hand on a small nested trace kept in the repository
(``data/scoped_nested.pbtxt`` and the HLO it ran,
``data/scoped_nested.hlo``), and the readers of the step's scopes:
numbers where the program names its scopes, None where it does not (a
program from before the scopes), and the older readers unchanged on the
trace recorded before the scopes (``data/rff16.xplane.pb``)."""
import json
import os

import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("predict_update_us", "check_us", "sync_us")


@pytest.fixture(scope="module")
def hlo():
    with open(os.path.join(DATA, "scoped_nested.hlo")) as f:
        return f.read()


@pytest.fixture(scope="module")
def nested():
    with open(os.path.join(DATA, "scoped_nested.pbtxt")) as f:
        data = ProfileData.from_text_proto(f.read())
    return data, trace.reduce(data, {})


def test_scope_names_from_hlo(hlo):
    assert scopes.scope_names(hlo) == {
        "mul.1": "predict_update", "fusion.pu": "predict_update",
        "reduce.1": "check", "fusion.chk": "check", "tuple.2": "check",
        "cond.check": "check",
        "add.2": "sync", "all-gather.1": "sync", "fusion.s": "sync",
        "cond.sync": "sync"}


def test_scope_roots_are_the_outermost(hlo):
    # fusion.chk and the sync branch's operations run inside their cond;
    # the fused computations' instructions inside their fusion
    assert scopes.scope_roots(hlo) == {
        "fusion.pu": "predict_update", "cond.check": "check", "cond.sync": "sync"}


def test_first_scope_component_wins():
    line = ('  %f.1 = f32[] add(%a, %b), metadata={op_name='
            '"jit(f)/while/body/sync/cond/branch_1_fun/checker/predict_update/add"}')
    assert scopes.scope_names("%c (a: f32[]) -> f32[] {\n" + line + "\n}") == {"f.1": "sync"}


def test_no_scopes_in_an_unscoped_program():
    line = '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(f)/while/body/mul"}'
    text = "ENTRY %main (p: f32[8]) -> f32[8] {\n" + line + "\n}"
    assert scopes.scope_names(text) == {} and scopes.scope_roots(text) == {}


def test_scope_time_counts_nothing_twice(hlo, nested):
    _, summary = nested
    (d,) = summary.devices
    # while [3000, 15000) is the one busy stretch; inside it fusion.pu
    # twice (1000 each), cond.check 2000 holding fusion.chk, cond.sync
    # 6000 holding all-gather.1 and fusion.s, fusion.x 2000 unscoped
    assert d.busy_ns == 12000
    ns = scopes.scope_ns(d.ops_ns, scopes.scope_roots(hlo))
    assert ns == {"predict_update": 2000, "check": 2000, "sync": 6000}
    assert sum(ns.values()) <= d.busy_ns


def test_idle_inside_the_host_spans(nested):
    data, summary = nested
    spans = scopes.host_spans(data)
    assert [s[2] for s in spans] == [scopes.RUN_SPAN, *scopes.PHASE_SPANS]
    idle = scopes.idle_in_spans(summary, spans)
    # gaps [0, 3000) and [15000, 20000) of the window
    assert idle == {"repro.engine.run": 2000 + 4000, "repro.engine.upload": 1000,
                    "repro.engine.dispatch": 1000, "repro.engine.copy_back": 3000,
                    "repro.engine.assemble": 1000}
    assert sum(idle[s] for s in scopes.PHASE_SPANS) == idle[scopes.RUN_SPAN]


def _reading(summary, kind="dynamic", syncs=2, rounds=4):
    cfg = {"kind": "sv", "learners": 8, "chips": 1, "budget": 4, "dim": 2}
    return trace.Reading(cfg=cfg, traffic={"protocol": {"kind": kind, "mini_batch": 2}},
                         peak=None, summary=summary, rounds=rounds, syncs=syncs)


def test_readers(hlo, nested, monkeypatch):
    _, summary = nested
    monkeypatch.setattr(scopes, "program_roots", lambda cfg, traffic: scopes.scope_roots(hlo))
    read = {m: harness.load_reader(m) for m in NEW}
    r = _reading(summary)
    assert read["predict_update_us"](r) == pytest.approx(2000 / 1e3 / 4)
    assert read["check_us"](r) == pytest.approx(2000 / 1e3 / 2)     # mini_batch 2
    assert read["sync_us"](r) == pytest.approx(6000 / 1e3 / 2)
    assert read["check_us"](_reading(summary, kind="periodic")) is None
    assert read["sync_us"](_reading(summary, syncs=0)) is None


def test_readers_of_an_unscoped_program(nested, monkeypatch):
    """A program without the scopes (one from before them) gives None."""
    _, summary = nested
    monkeypatch.setattr(scopes, "program_roots", lambda cfg, traffic: {})
    for m in NEW:
        assert harness.load_reader(m)(_reading(summary)) is None


#: every older reader's number on the trace recorded before the scopes,
#: as the benchmark read it before they came
BEFORE = {"device_idle_share": 96.26991840292114, "round_busy_us": 15.5715625,
          "step_mfu": 0.0011288919707067838, "sv_predict_roofline": None,
          "quadform_roofline": None, "rff_step_roofline": 8.301852272200447,
          "collective_share": None}


def test_older_readers_unchanged_on_the_older_trace(monkeypatch):
    with open(os.path.join(DATA, "rff16.json")) as f:
        meta = json.load(f)
    summary = trace.reduce(trace.load(os.path.join(DATA, "rff16.xplane.pb")),
                           meta["kernels"], 1)
    cell = harness.resolve("susy-rff1024.dynamic")
    r = trace.Reading(cfg=dict(cell.cfg, rounds=meta["rounds"]), traffic=cell.traffic,
                      peak=harness.load_peaks()["TPU v5 lite"], summary=summary,
                      rounds=meta["experiments"] * meta["rounds"], syncs=10)
    assert {m: harness.load_reader(m)(r) for m in BEFORE} == BEFORE
    assert summary.breakdown()["device_ops"][0] == ["while.2", 0.000497153]
    monkeypatch.setattr(scopes, "program_roots", lambda cfg, traffic: {})
    assert [harness.load_reader(m)(r) for m in NEW] == [None, None, None]
