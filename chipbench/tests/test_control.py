"""The control: the plain reference computed at the next precision below
the configuration's (``high``, three bfloat16 passes, for float32 at
``highest``), put in the system's place.  The harness has to find it
not correct with every cell's own limits, at the cell's own size, on
three seeds.

The control's arithmetic is the chip's: on a TPU v5 lite the
three-pass products part from float32 far enough to flip the
protocol's decisions within 1000 rounds, where on the CPU the same
products move the summed losses by about 1e-6 and flip no decision
(PERF.md, section 2).  So this test runs where JAX has a TPU, and
skips elsewhere:

  python3 -m pytest chipbench/tests/test_control.py
"""
import time
import types

import jax
import pytest

import readings
from chipbench import harness

from conftest import REPO

CELLS = ("susy-rff1024.dynamic", "susy-sv512.dynamic", "susy-sv512.periodic")
SEEDS = (2147483001, 2147483002, 2147483003)


class ControlSystem:
    """The reference at ``high`` precision, with the system's face."""

    def __init__(self, cfg, protocol):
        self.cfg, self.protocol = cfg, protocol
        self.ref = harness.reference_module(cfg)

    def run(self, X, Y):
        return self.ref.run(self.cfg, self.protocol, X, Y, "high")

    @staticmethod
    def series(o):
        return readings.control_series(o)

    def hlo_text(self, X, Y):
        return ""


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(monkeypatch, workload, seed):
    if jax.default_backend() != "tpu":
        pytest.skip("the control is read on a TPU: on the CPU its products "
                    "flip no decision")
    monkeypatch.setattr(harness, "system_module", lambda cfg: types.SimpleNamespace(
        build=lambda cfg, protocol: ControlSystem(cfg, protocol)))
    out = harness.run(workload, seed, 0.1, False, t_start=time.perf_counter(),
                      root=REPO)
    over = {k: c for k, c in out["checks"].items()
            if not k.startswith("min_") and c["value"] > c["limit"]}
    print(workload, seed, out["checks"])
    assert out["correct"] is False and over, out["checks"]
