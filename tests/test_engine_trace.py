"""The engine's names in a JAX profiler trace (DESIGN.md Sec. 11).

- the protocol step runs its phases under ``jax.named_scope``
  (``probe.STEP_SCOPES``), which reach the compiled program as HLO
  ``op_name`` metadata: predict/update and sync in every protocol kind
  that syncs, the check only in the dynamic one;
- ``engine.run`` opens the host spans ``probe.ENGINE_RUN`` and, inside
  it and in order, ``probe.ENGINE_PHASES``, found in the profiler's
  ``.xplane.pb`` on the CPU as on a chip;
- a profiled run returns the bitwise result of an unprofiled one;
- ``repro.core.engine`` imports ``repro.telemetry.probe`` while
  ``repro.telemetry`` imports ``repro.core``: both import orders work.
"""
import dataclasses
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rff import RFFSpec
from repro.core.rkhs import KernelSpec
from repro.core.substrate import substrate_of
from repro.data.streams import susy_stream
from repro.telemetry import probe

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
D, T, M = 8, 24, 4
LEARNERS = {
    "sv": LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                        budget=16, kernel=KernelSpec("gaussian", gamma=0.3),
                        dim=D),
    "rff": RFFSpec(dim=D, num_features=32, gamma=0.3, seed=0),
}
PROTOCOLS = {"dynamic": ProtocolConfig(kind="dynamic", delta=1.0),
             "periodic": ProtocolConfig(kind="periodic", period=5)}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scopes_in(hlo_text: str) -> set:
    """The step scopes that are whole components of some op_name path."""
    return {c for path in _OP_NAME.findall(hlo_text) for c in path.split("/")
            if c in probe.STEP_SCOPES}


@pytest.mark.parametrize("kind", sorted(PROTOCOLS))
@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_compiled_step_carries_the_phase_scopes(learner, kind):
    sub = substrate_of(LEARNERS[learner])
    X, Y = susy_stream(T, M, D, seed=0)
    fn = engine._jitted(sub, kind, False, False, False)
    hlo = fn.lower(engine.params_of(PROTOCOLS[kind]), jnp.asarray(X),
                   jnp.asarray(Y)).compile().as_text()
    want = {probe.SCOPE_PREDICT_UPDATE, probe.SCOPE_SYNC}
    if kind == "dynamic":
        want.add(probe.SCOPE_CHECK)
    assert _scopes_in(hlo) == want


def _xplane(tracedir: str) -> str:
    (path,) = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                        recursive=True)
    return path


def test_run_opens_its_host_spans_in_order(tmp_path):
    from jax.profiler import ProfileData

    X, Y = susy_stream(T, M, D, seed=1)
    engine.run(LEARNERS["rff"], PROTOCOLS["dynamic"], X, Y)    # compile
    with jax.profiler.trace(str(tmp_path)):
        engine.run(LEARNERS["rff"], PROTOCOLS["dynamic"], X, Y)
    data = ProfileData.from_file(_xplane(str(tmp_path)))
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("repro.engine."))
    (run,) = [s for s in spans if s[2] == probe.ENGINE_RUN]
    phases = [s for s in spans if s[2] != probe.ENGINE_RUN]
    assert [s[2] for s in phases] == list(probe.ENGINE_PHASES)
    for (s0, s1, _), (t0, _, _) in zip(phases, phases[1:]):
        assert s1 <= t0                        # one after the other
    assert run[0] <= phases[0][0] and phases[-1][1] <= run[1]


def test_profiled_run_is_bitwise_the_unprofiled_one(tmp_path):
    X, Y = susy_stream(T, M, D, seed=2)
    pcfg = PROTOCOLS["dynamic"]
    plain = engine.run(LEARNERS["sv"], pcfg, X, Y)
    with jax.profiler.trace(str(tmp_path)):
        traced = engine.run(LEARNERS["sv"], pcfg, X, Y)
    assert plain.num_syncs > 0
    for f in dataclasses.fields(plain):
        a, b = getattr(plain, f.name), getattr(traced, f.name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f.name)
        assert np.asarray(a).dtype == np.asarray(b).dtype


@pytest.mark.parametrize("first,then", [
    ("repro.telemetry", "repro.core.engine"),
    ("repro.core.engine", "repro.telemetry"),
])
def test_import_order(first, then):
    code = (f"import {first}, {then}\n"
            "from repro.core import engine\n"
            "from repro.telemetry import probe, CompileCounter\n"
            "assert engine.probe is probe\n"
            "print(probe.ENGINE_RUN)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == probe.ENGINE_RUN
