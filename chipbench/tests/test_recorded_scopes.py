"""The program's names on a trace recorded on a TPU v5 lite
(``data/rff16_scoped.xplane.pb``): two experiments of ``susy-rff1024``
cut to 16 rounds, run by the harness's window with its spans
(``record_trace.py``), and the kernel, scope and outermost-scope maps
the compiled program's HLO gave (``data/rff16_scoped.json``).  The
step's three scopes show on the device, ``engine.run``'s spans on the
host, and the two are on one clock to about a millisecond: every device
operation of an experiment runs inside that experiment's
``repro.engine.run`` span."""
import json
import os

import pytest

from chipbench import harness, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "rff16_scoped.json")) as f:
        meta = json.load(f)
    data = trace.load(os.path.join(DATA, "rff16_scoped.xplane.pb"))
    return meta, data, trace.reduce(data, meta["kernels"], 1)


def _ops(data, names=None):
    """[start, end) of the chip's operations, or of those named."""
    out = []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    out += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events
                            if names is None or trace.op_name(e.name) in names]
    return out


def test_maps(recorded):
    meta, _, _ = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    roots, names = meta["scope_roots"], meta["scope_names"]
    assert roots and all(names[k] == v for k, v in roots.items())
    assert set(roots.values()) == set(scopes.SCOPES)
    assert roots["_primal_step_call.6"] == "predict_update"     # the rff_step launch


def test_every_scope_on_the_chip_and_none_counted_twice(recorded):
    meta, data, summary = recorded
    (device,) = summary.devices
    ns = scopes.scope_ns(device.ops_ns, meta["scope_roots"])
    assert all(ns[s] > 0 for s in scopes.SCOPES)
    assert sum(ns.values()) < device.busy_ns
    # the outermost scoped operations never overlap: their union is their sum
    spans = _ops(data, meta["scope_roots"])
    union, _ = trace.union_ns(spans)
    assert union == sum(e - s for s, e in spans)


def _experiments(data):
    spans = scopes.host_spans(data)
    runs = [s for s in spans if s[2] == scopes.RUN_SPAN]
    out = []
    for r0, r1, _ in runs:
        inside = [s for s in spans if s[2] != scopes.RUN_SPAN and r0 <= s[0] and s[1] <= r1]
        out.append(((r0, r1), inside))
    return out


def test_engine_spans_in_order(recorded):
    meta, data, _ = recorded
    exps = _experiments(data)
    assert len(exps) == meta["experiments"]
    for _, inside in exps:
        assert [s[2] for s in inside] == list(scopes.PHASE_SPANS)
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))


def _modules(data):
    """[start, end) of the chip's runs of the engine's program."""
    out = []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events if e.name.startswith("jit_simulate")]
    return sorted(out)


def test_device_and_host_share_one_clock_to_a_few_ms(recorded):
    """Each experiment's program runs on the chip inside that experiment's
    ``repro.engine.run`` span and before its copy-back ends.  The planes
    are aligned to about a millisecond, not better: on this recording the
    program shows on the chip 0.5-0.7 ms before the host's dispatch span
    opens (and 0.9-1.1 ms before the host launches it), so an idle gap
    near a span's edge may fall to the neighbouring span."""
    _, data, _ = recorded
    exps = _experiments(data)
    modules = _modules(data)
    assert len(modules) == len(exps)
    for ((r0, r1), inside), (m0, m1) in zip(exps, modules):
        dispatch, copy_back = inside[1], inside[2]
        assert r0 < m0 and m1 < copy_back[1]
        assert 0 < dispatch[0] - m0 < 2_000_000        # the device leads by < 2 ms
    # every operation in the window belongs to one of the programs, or to
    # the small conversions of the upload (inside the run spans)
    ((w0, w1),) = [(s[0], s[1]) for s in scopes.host_spans(data, trace.WINDOW_SPAN)]
    for s, e in _ops(data):
        if s < w1 and e > w0:
            assert any(r0 <= s and e <= r1 for (r0, r1), _ in exps), (s, e)


def test_idle_by_span(recorded):
    _, data, summary = recorded
    idle = scopes.idle_in_spans(summary, scopes.host_spans(data))
    (device,) = summary.devices
    window_idle = summary.window_ns - device.busy_ns
    assert 0 < idle[scopes.RUN_SPAN] <= window_idle
    assert sum(idle[s] for s in scopes.PHASE_SPANS) <= idle[scopes.RUN_SPAN]
    assert idle["repro.engine.copy_back"] > 0 and idle["repro.engine.upload"] > 0


def test_readers(recorded, monkeypatch):
    meta, _, summary = recorded
    monkeypatch.setattr(scopes, "program_roots", lambda cfg, traffic: meta["scope_roots"])
    cell = harness.resolve("susy-rff1024.dynamic")
    rounds = meta["experiments"] * meta["rounds"]
    r = trace.Reading(cfg=dict(cell.cfg, rounds=meta["rounds"]), traffic=cell.traffic,
                      peak=None, summary=summary, rounds=rounds, syncs=meta["syncs"])
    ns = scopes.scope_ns(summary.devices[0].ops_ns, meta["scope_roots"])
    assert harness.load_reader("predict_update_us")(r) == ns["predict_update"] / 1e3 / rounds
    assert harness.load_reader("check_us")(r) == ns["check"] / 1e3 / rounds
    assert harness.load_reader("sync_us")(r) == ns["sync"] / 1e3 / meta["syncs"]
