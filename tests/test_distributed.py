"""Distributed integration test: the dynamic protocol on a real
multi-device host mesh (8 CPU devices in a subprocess — jax locks the
device count at first init, so this must run out-of-process)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs import get
    from repro.core.protocol import ProtocolConfig
    from repro.launch.train import init_train_state, make_train_step
    from repro.launch import sharding as shd
    from repro.optim import OptimizerConfig

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    m = 4

    cfg = get("qwen2_5_3b").smoke()
    pcfg = ProtocolConfig(kind="dynamic", delta=1e-4)
    opt_cfg = OptimizerConfig(kind="sgd", lr=0.05)
    state = init_train_state(jax.random.PRNGKey(0), cfg, m, opt_cfg)

    pspec = shd.param_pspec(state.params, 2, learner_axes=("data",))
    opt_pspec = shd.param_pspec(state.opt, 2, learner_axes=("data",))
    from repro.core.protocol import ProtocolState
    from repro.launch.train import TrainState
    state_pspec = TrainState(
        params=pspec, opt=opt_pspec,
        pstate=ProtocolState(
            reference=shd.param_pspec(state.pstate.reference, 2,
                                      learner_axes=("data",)),
            step=P(), syncs=P(), bytes_sent=P(), last_divergence=P(),
            delta_scale=P()),
        step=P())

    step_fn = jax.jit(
        make_train_step(cfg, pcfg, opt_cfg),
        in_shardings=(shd.to_shardings(mesh, state_pspec), None),
        out_shardings=(shd.to_shardings(mesh, state_pspec), None),
    )

    rng = np.random.default_rng(0)
    with mesh:
        state = jax.device_put(state, shd.to_shardings(mesh, state_pspec))
        losses = []
        for t in range(6):
            toks = rng.integers(0, cfg.vocab, (m, 2, 17))
            batch = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
                     "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
            state, loss = step_fn(state, batch)
            losses.append(float(loss))

    assert all(np.isfinite(losses)), losses
    assert int(state.pstate.syncs) >= 1      # tiny delta forces syncs
    # all learners hold identical models after a sync round
    from repro.core import protocol
    div = float(protocol.divergence(state.params))
    print("OK syncs=", int(state.pstate.syncs), "div=", div)
""")


@pytest.mark.slow
def test_dynamic_protocol_on_host_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK syncs=" in r.stdout
