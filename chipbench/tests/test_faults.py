"""The harness, off the chip and at CPU size, with the timed path broken
underneath: every fault a cell can have has to turn ``correct`` false,
and the unbroken path has to stay correct.

The faults are planted in the system under test (the program's
substrate classes and engine), never in the reference.
"""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from chipbench import harness
from chipbench.systems.engine import import_program

from conftest import REPO, tiny_checkout

import_program()
from repro.core import engine  # noqa: E402
from repro.core import substrate as sub_mod  # noqa: E402


def run_cell(root, workload, seed=7):
    engine._jitted.cache_clear()
    try:
        return harness.run(workload, seed, 0.3, False, t_start=time.perf_counter(),
                           root=root, require_chip=False)
    finally:
        engine._jitted.cache_clear()


def unchanged_state(cls):
    orig = cls.round_stacked

    def round_stacked(self, state, example):
        _, losses, yhat = orig(self, state, example)
        return state, losses, yhat
    return round_stacked


def altered_answer(cls):
    orig = cls.round_stacked

    def round_stacked(self, state, example):
        new, losses, yhat = orig(self, state, example)
        return new, losses.at[0].add(1e-3), yhat
    return round_stacked


def half_batch(cls):
    orig = cls.average_stacked

    def average_stacked(self, models):
        half = jax.tree.map(lambda v: v[: v.shape[0] // 2], models)
        return orig(self, half)
    return average_stacked


CELLS = {"susy-sv512.dynamic": sub_mod.SVSubstrate,
         "susy-sv512.periodic": sub_mod.SVSubstrate,
         "susy-rff1024.dynamic": sub_mod.RFFSubstrate}
FAULTS = {"unchanged_state": ("round_stacked", unchanged_state),
          "altered_answer": ("round_stacked", altered_answer),
          "half_batch": ("average_stacked", half_batch)}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_unbroken_is_correct(tiny, workload):
    out = run_cell(tiny, workload)
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    assert out["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_is_caught(tiny, monkeypatch, workload, fault):
    cls = CELLS[workload]
    attr, make = FAULTS[fault]
    monkeypatch.setattr(cls, attr, make(cls))
    out = run_cell(tiny, workload)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] == out["attempted"]


MESH_SCRIPT = r"""
import json, sys, time, types
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from chipbench import harness
from chipbench.systems.engine import import_program
import_program()
from repro.core import engine
if {broken!r}:
    def all_gather(x, axis_name, *, axis=0, tiled=False):
        # the exchange left out: every chip "gathers" its own slice
        return jnp.concatenate([x] * 4, axis=axis)
    engine.lax = types.SimpleNamespace(**dict(vars(jax.lax), all_gather=all_gather))
out = harness.run("susy-sv512-mesh4.dynamic", 7, 0.3, False,
                  t_start=time.perf_counter(), root={root!r}, require_chip=False)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True], ids=["unbroken", "exchange_left_out"])
def test_mesh_exchange(tmp_path, broken):
    root = tiny_checkout(tmp_path / "checkout", learners=8)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT.format(repo=REPO, root=root, broken=broken)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["checks"]


def test_run_refuses_cpu():
    """The command exits non-zero and prints no result off a TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"), "--workload",
         "susy-sv512.dynamic", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
