"""Mesh-sharded scan engine vs the single-device engine (DESIGN.md
Sec. 9).

The contract under test: with the learner axis sharded over 8 forced
host devices, ``engine.run(..., mesh=...)`` reproduces the
single-device engine BIT-FOR-BIT on losses / errors / divergences and
integer-exactly on the byte ledger, for {dynamic, periodic} x
{SV, RFF, linear}; ``engine.sweep(..., mesh=...)`` does the same for a
mixed-kind grid; and ``topology="allreduce"`` prices every sync at the
fixed ring total of ``Substrate.allreduce_sync_bytes`` without
changing a single decision.

jax locks the device count at first init, so the multi-device half
runs out-of-process (the established pattern of
tests/test_distributed.py); mesh/topology *validation* runs
in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np

    from repro.core import accounting, engine
    from repro.core.learners import LearnerConfig
    from repro.core.protocol import ProtocolConfig
    from repro.core.rff import RFFSpec
    from repro.core.rkhs import KernelSpec
    from repro.core.substrate import substrate_of
    from repro.data import susy_stream
    from repro.launch.mesh import make_learner_mesh

    assert len(jax.devices()) == 8
    mesh = make_learner_mesh()
    T, M, D = 40, 8, 6
    X, Y = susy_stream(T=T, m=M, d=D, seed=3)

    kcfg = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5,
                         lam=0.01, budget=12,
                         kernel=KernelSpec("gaussian", gamma=0.3), dim=D)
    lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                         lam=0.001, dim=D)
    rspec = RFFSpec(dim=D, num_features=32, gamma=0.3, seed=0)

    def assert_bit_identical(r1, r8, tag):
        for field in ("cumulative_loss", "cumulative_errors",
                      "cumulative_bytes", "sync_rounds", "divergences",
                      "eps_history"):
            a, b = getattr(r1, field), getattr(r8, field)
            assert np.array_equal(a, b), (tag, field, a, b)
        assert r1.num_syncs == r8.num_syncs, tag
        assert r1.total_bytes == r8.total_bytes, tag

    protos = [ProtocolConfig(kind="dynamic", delta=1.0),
              ProtocolConfig(kind="periodic", period=7)]
    for name, learner in [("sv", kcfg), ("rff", rspec), ("linear", lcfg)]:
        for pcfg in protos:
            r1 = engine.run(learner, pcfg, X, Y, record_divergence=True)
            r8 = engine.run(learner, pcfg, X, Y, record_divergence=True,
                            mesh=mesh)
            assert r1.num_syncs > 0, (name, pcfg.kind)
            assert_bit_identical(r1, r8, f"{name}/{pcfg.kind}")

    # sweep: config axis vmapped x learner axis sharded, mixed kinds
    grid = [ProtocolConfig(kind="dynamic", delta=d) for d in (0.5, 1.0, 2.0)]
    grid.append(ProtocolConfig(kind="periodic", period=5))
    sw1 = engine.sweep(kcfg, grid, X, Y)
    sw8 = engine.sweep(kcfg, grid, X, Y, mesh=mesh)
    for i in range(len(grid)):
        assert np.array_equal(sw1[i].cumulative_loss, sw8[i].cumulative_loss)
        assert np.array_equal(sw1[i].cumulative_bytes, sw8[i].cumulative_bytes)
        assert np.array_equal(sw1[i].sync_rounds, sw8[i].sync_rounds)

    # topology="allreduce": identical decisions, ring-total pricing
    for name, learner in [("sv", kcfg), ("rff", rspec), ("linear", lcfg)]:
        sub = substrate_of(learner)
        pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
        rc = engine.run(learner, pcfg, X, Y, mesh=mesh)
        ra = engine.run(learner, pcfg, X, Y, mesh=mesh,
                        topology="allreduce")
        assert np.array_equal(rc.sync_rounds, ra.sync_rounds), name
        assert np.array_equal(rc.cumulative_loss, ra.cumulative_loss), name
        per_sync = sub.allreduce_sync_bytes(M)
        assert ra.total_bytes == ra.num_syncs * per_sync, name
    # the linear/RFF ring totals are the fixed accounting.allreduce_bytes
    assert substrate_of(lcfg).allreduce_sync_bytes(M) == \\
        accounting.allreduce_bytes(D + 1, M)
    assert substrate_of(rspec).allreduce_sync_bytes(M) == \\
        accounting.allreduce_bytes(32 + 1, M)

    print("OK mesh parity")
""")


@pytest.mark.slow
def test_sharded_engine_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK mesh parity" in r.stdout


# ---------------------------------------------------------------------------
# in-process validation (single default device is fine)
# ---------------------------------------------------------------------------


def test_learner_axes_resolution():
    import jax
    from jax.sharding import AxisType

    from repro.core.engine import learner_axes_of

    def mesh_of(shape, axes):
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))

    mesh = mesh_of((1,), ("learners",))
    assert learner_axes_of(mesh) == ("learners",)
    mesh = mesh_of((1, 1), ("data", "model"))
    assert learner_axes_of(mesh) == ("data",)
    mesh = mesh_of((1,), ("model",))
    with pytest.raises(ValueError, match="learner axis"):
        learner_axes_of(mesh)


def test_run_validates_topology_and_single_shard_mesh():
    from repro.core import engine
    from repro.core.learners import LearnerConfig
    from repro.core.protocol import ProtocolConfig
    from repro.data import separable_stream
    from repro.launch.mesh import make_learner_mesh

    lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", dim=6)
    X, Y = separable_stream(T=5, m=3, d=6, seed=0)
    with pytest.raises(ValueError, match="topology"):
        engine.run(lcfg, ProtocolConfig(kind="dynamic"), X, Y,
                   topology="ring")
    mesh = make_learner_mesh(1)
    # m divides over 1 device: must run (and agree with the meshless run)
    r = engine.run(lcfg, ProtocolConfig(kind="periodic", period=2), X, Y,
                   mesh=mesh)
    r0 = engine.run(lcfg, ProtocolConfig(kind="periodic", period=2), X, Y)
    np.testing.assert_array_equal(r.cumulative_loss, r0.cumulative_loss)
    np.testing.assert_array_equal(r.cumulative_bytes, r0.cumulative_bytes)
