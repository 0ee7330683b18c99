"""The trace reduction on a trace recorded on a TPU v5 lite
(``data/rff16.xplane.pb``): two experiments of ``susy-rff1024`` cut to
16 rounds, run by the harness's window with its spans, and the
kernel map the compiled program's HLO gave (``data/rff16.json``).
On the chip every ``XLA Ops`` event is named by its whole HLO
instruction; the reduction has to find the window, one device and one
``rff_step`` launch per round."""
import json
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "rff16.json")) as f:
        meta = json.load(f)
    data = trace.load(os.path.join(DATA, "rff16.xplane.pb"))
    return meta, trace.reduce(data, meta["kernels"], 1)


def test_kernel_map_names_the_rff_step(recorded):
    meta, _ = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    assert sorted(meta["kernels"].values()) == ["_rff_step_kernel"]


def test_one_launch_per_round(recorded):
    meta, summary = recorded
    (device,) = summary.devices
    assert device.kernels["_rff_step_kernel"][1] == meta["experiments"] * meta["rounds"]


def test_busy_inside_the_window(recorded):
    _, summary = recorded
    (device,) = summary.devices
    assert 0 < device.busy_ns < summary.window_ns
    assert sum(e - s for s, e in device.gaps) == summary.window_ns - device.busy_ns


def test_operations_named_by_instruction(recorded):
    _, summary = recorded
    names = summary.devices[0].ops_ns
    assert names and not any("=" in n or n.startswith("%") for n in names)
    assert any(n.startswith("fusion") for n in names)
