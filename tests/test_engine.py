"""Scan engine (core/engine.py) vs the serial oracle, and the device
byte ledger vs the host ledger (DESIGN.md Sec. 7).

The contract under test: the device-resident engine reproduces the
legacy Python-loop driver's byte ledger *exactly* (cumulative_bytes
identical, sync decisions identical) and its losses / errors /
divergences to float32 tolerance.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accounting, engine, rkhs, simulation
from repro.core.accounting import ByteModel, CommunicationLedger
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rkhs import KernelSpec
from repro.data import separable_stream, susy_stream


# ---------------------------------------------------------------------------
# sorted-id set algebra
# ---------------------------------------------------------------------------


def test_sorted_unique_counts_and_composes():
    ids = jnp.asarray([[5, -1, 3, 5], [3, 7, -1, -1]], jnp.int32)
    uniq, n = rkhs.sorted_unique(ids)
    assert int(n) == 3
    np.testing.assert_array_equal(
        np.asarray(uniq)[:3], [3, 5, 7])
    assert (np.asarray(uniq)[3:] == int(rkhs.ID_SENTINEL)).all()
    # output is a valid input (sentinel slots stay inactive)
    uniq2, n2 = rkhs.sorted_unique(uniq)
    assert int(n2) == 3
    np.testing.assert_array_equal(np.asarray(uniq), np.asarray(uniq2))


def test_count_members():
    a, _ = rkhs.sorted_unique(jnp.asarray([2, 4, 6, -1, -1], jnp.int32))
    q, _ = rkhs.sorted_unique(jnp.asarray([4, 5, 6, -1, -1], jnp.int32))
    assert int(rkhs.count_members(q, a)) == 2
    empty, _ = rkhs.sorted_unique(jnp.asarray([-1, -1], jnp.int32))
    assert int(rkhs.count_members(empty, a)) == 0
    assert int(rkhs.count_members(q, jnp.sort(empty))) == 0


# ---------------------------------------------------------------------------
# DeviceLedger vs CommunicationLedger (byte-for-byte)
# ---------------------------------------------------------------------------


def _random_id_config(rng, m, tau, pool):
    """Random stacked sv_id array with empty slots, ids shared across
    learners (post-sync state), duplicated ids within one learner
    (adopted compressed average), and fresh ids (insertions)."""
    ids = np.full((m, tau), -1, np.int32)
    for i in range(m):
        n_active = int(rng.integers(0, tau + 1))
        chosen = []
        for _ in range(n_active):
            if pool and rng.random() < 0.6:
                chosen.append(int(rng.choice(pool)))   # shared / duplicate
            else:
                fresh = int(rng.integers(0, 100_000))
                pool.append(fresh)
                chosen.append(fresh)
        slots = rng.permutation(tau)[:n_active]
        ids[i, slots] = chosen
    return ids


def _assert_ledgers_agree(seed, m=3, tau=7, n_syncs=6):
    rng = np.random.default_rng(seed)
    bm = ByteModel(dim=5)
    host = CommunicationLedger(bm)
    dev = accounting.device_ledger_init(m * tau)
    pool = []
    for t in range(n_syncs):
        ids = _random_id_config(rng, m, tau, pool)
        b_host = host.record_kernel_sync([ids[i] for i in range(m)], t)
        b_dev, dev = accounting.device_sync_bytes_kernel(
            bm, jnp.asarray(ids), dev)
        assert int(b_dev) == b_host, f"sync {t}: {int(b_dev)} != {b_host}"
    known_dev = np.asarray(dev.known)
    known_dev = set(known_dev[known_dev < int(rkhs.ID_SENTINEL)].tolist())
    assert known_dev == host.coordinator_known


@pytest.mark.parametrize("seed", range(8))
def test_device_ledger_matches_host_ledger(seed):
    _assert_ledgers_agree(seed)


def test_device_ledger_empty_and_full():
    bm = ByteModel(dim=3)
    m, tau = 2, 4
    dev = accounting.device_ledger_init(m * tau)
    empty = np.full((m, tau), -1, np.int32)
    b, dev = accounting.device_sync_bytes_kernel(bm, jnp.asarray(empty), dev)
    assert int(b) == 0
    # all slots active, all distinct: first sync ships everything
    ids = np.arange(m * tau, dtype=np.int32).reshape(m, tau)
    b, dev = accounting.device_sync_bytes_kernel(bm, jnp.asarray(ids), dev)
    host = CommunicationLedger(bm)
    b_host = host.record_kernel_sync([ids[i] for i in range(m)], 0)
    assert int(b) == b_host
    # re-syncing the identical configuration re-ships no vectors
    b2, dev = accounting.device_sync_bytes_kernel(bm, jnp.asarray(ids), dev)
    b2_host = host.record_kernel_sync([ids[i] for i in range(m)], 1)
    assert int(b2) == b2_host
    assert int(b2) < int(b)


def test_device_ledger_refuses_int32_overflow_scales():
    bm = ByteModel(dim=1000)
    m, tau = 64, 4096
    dev = accounting.device_ledger_init(m * tau)
    ids = np.full((m, tau), -1, np.int32)
    with pytest.raises(ValueError, match="int32"):
        accounting.device_sync_bytes_kernel(bm, jnp.asarray(ids), dev)


def test_device_ledger_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def inner(seed):
        _assert_ledgers_agree(seed, m=4, tau=5, n_syncs=4)

    inner()


# ---------------------------------------------------------------------------
# engine.run vs the serial oracle
# ---------------------------------------------------------------------------

T, M, D = 70, 3, 6


def _kernel_cfg(budget=12, **kw):
    return LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                         budget=budget,
                         kernel=KernelSpec("gaussian", gamma=0.3), dim=D, **kw)


def _assert_matches_oracle(res_loop, res_eng, check_div=True):
    np.testing.assert_array_equal(res_loop.cumulative_bytes,
                                  res_eng.cumulative_bytes)
    np.testing.assert_array_equal(res_loop.sync_rounds, res_eng.sync_rounds)
    assert res_loop.num_syncs == res_eng.num_syncs
    np.testing.assert_allclose(res_loop.cumulative_loss,
                               res_eng.cumulative_loss, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(res_loop.cumulative_errors,
                                  res_eng.cumulative_errors)
    assert abs(res_loop.total_loss - res_eng.total_loss) <= \
        1e-5 * max(1.0, abs(res_loop.total_loss))
    if check_div and len(res_eng.divergences):
        np.testing.assert_allclose(res_loop.divergences, res_eng.divergences,
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pcfg", [
    ProtocolConfig(kind="dynamic", delta=2.0),
    ProtocolConfig(kind="dynamic", delta=1.0, mini_batch=4),
    ProtocolConfig(kind="periodic", period=9),
    ProtocolConfig(kind="continuous"),
    ProtocolConfig(kind="none"),
], ids=lambda p: f"{p.kind}-d{p.delta}-b{p.period}-mb{p.mini_batch}")
def test_engine_matches_kernel_oracle(pcfg):
    X, Y = susy_stream(T=T, m=M, d=D, seed=3)
    lcfg = _kernel_cfg()
    res_loop = simulation.run_kernel_simulation(lcfg, pcfg, X, Y)
    res_eng = engine.run(lcfg, pcfg, X, Y, record_divergence=True)
    _assert_matches_oracle(res_loop, res_eng)
    np.testing.assert_allclose(res_loop.eps_history, res_eng.eps_history,
                               rtol=1e-4, atol=1e-5)


def test_engine_matches_kernel_oracle_projection_and_budget():
    X, Y = susy_stream(T=50, m=M, d=D, seed=5)
    lcfg = _kernel_cfg(budget=10)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    res_loop = simulation.run_kernel_simulation(
        lcfg, pcfg, X, Y, sync_budget=6, compress_method="project")
    res_eng = engine.run(lcfg, pcfg, X, Y, sync_budget=6,
                         compress_method="project", record_divergence=True)
    _assert_matches_oracle(res_loop, res_eng)


@pytest.mark.parametrize("pcfg", [
    ProtocolConfig(kind="dynamic", delta=1.0),
    ProtocolConfig(kind="periodic", period=10),
    ProtocolConfig(kind="continuous"),
], ids=lambda p: p.kind)
def test_engine_matches_linear_oracle(pcfg):
    X, Y = separable_stream(T=T, m=M, d=D, seed=0, margin=1.0)
    lcfg = LearnerConfig(algo="linear_pa", loss="hinge", C=1.0, dim=D)
    res_loop = simulation.run_linear_simulation(lcfg, pcfg, X, Y)
    res_eng = engine.run(lcfg, pcfg, X, Y)
    _assert_matches_oracle(res_loop, res_eng)
    assert len(res_eng.eps_history) == 0


def test_topology_allreduce_same_decisions_ring_pricing():
    """topology="allreduce" swaps the Sec. 3 coordinator pricing for
    the mesh ring total (DESIGN.md Sec. 9) without touching a single
    sync decision — with or without a mesh."""
    from repro.core import accounting
    from repro.core.substrate import substrate_of

    X, Y = susy_stream(T=60, m=M, d=D, seed=2)
    for learner in [_kernel_cfg(),
                    LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                                  lam=0.001, dim=D)]:
        sub = substrate_of(learner)
        pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
        rc = engine.run(learner, pcfg, X, Y)
        ra = engine.run(learner, pcfg, X, Y, topology="allreduce")
        np.testing.assert_array_equal(rc.sync_rounds, ra.sync_rounds)
        np.testing.assert_array_equal(rc.cumulative_loss, ra.cumulative_loss)
        assert ra.num_syncs > 0
        assert ra.total_bytes == ra.num_syncs * sub.allreduce_sync_bytes(M)
    # the primal ring total IS the fixed accounting.allreduce_bytes
    lin = substrate_of(LearnerConfig(algo="linear_sgd", dim=D))
    assert lin.allreduce_sync_bytes(M) == accounting.allreduce_bytes(D + 1, M)


def test_round0_zero_margin_predicts_positive_in_every_driver():
    """The hinge decision rule is deterministic at a zero margin
    (yhat >= 0 -> +1): an untrained all-zero model errs exactly on the
    negative labels at round 0 — not on every label — identically in
    the engine, the serial oracle, and the async runtime."""
    from repro.runtime import (AsyncProtocolConfig, SystemConfig,
                               run_async_simulation)

    X, Y = susy_stream(T=3, m=M, d=D, seed=11)
    Y[0] = np.asarray([1.0, -1.0, 1.0], np.float32)   # mixed round-0 labels
    expected0 = float((Y[0] == -1).sum())
    lcfg = _kernel_cfg()
    pcfg = ProtocolConfig(kind="none")

    res_eng = engine.run(lcfg, pcfg, X, Y)
    res_loop = simulation.run_kernel_simulation(lcfg, pcfg, X, Y)
    res_async = run_async_simulation(
        lcfg, AsyncProtocolConfig(kind="dynamic", delta=1e9), X, Y,
        sys_cfg=SystemConfig(), record_divergence=False)
    assert res_eng.cumulative_errors[0] == expected0
    assert res_loop.cumulative_errors[0] == expected0
    assert res_async.cumulative_errors[0] == expected0


def test_engine_divergence_recording_is_optional():
    X, Y = susy_stream(T=30, m=M, d=D, seed=7)
    res = engine.run(_kernel_cfg(), ProtocolConfig(kind="dynamic", delta=2.0),
                     X, Y)
    assert len(res.divergences) == 0
    assert len(res.cumulative_loss) == 30


# ---------------------------------------------------------------------------
# engine.sweep
# ---------------------------------------------------------------------------


def test_sweep_matches_solo_runs_mixed_kinds():
    X, Y = susy_stream(T=50, m=M, d=D, seed=1)
    lcfg = _kernel_cfg()
    grid = [
        ProtocolConfig(kind="dynamic", delta=0.5),
        ProtocolConfig(kind="dynamic", delta=2.0, mini_batch=5),
        ProtocolConfig(kind="periodic", period=7),
        ProtocolConfig(kind="continuous"),
    ]
    sw = engine.sweep(lcfg, grid, X, Y, record_divergence=True)
    assert len(sw) == len(grid)
    for i, p in enumerate(grid):
        solo = engine.run(lcfg, p, X, Y, record_divergence=True)
        got = sw[i]
        np.testing.assert_array_equal(solo.cumulative_bytes,
                                      got.cumulative_bytes)
        np.testing.assert_array_equal(solo.sync_rounds, got.sync_rounds)
        np.testing.assert_allclose(solo.cumulative_loss, got.cumulative_loss,
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(solo.divergences, got.divergences,
                                   rtol=1e-4, atol=1e-5)


def test_sweep_per_config_data_streams():
    lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1, lam=0.001,
                         dim=D)
    grid = [ProtocolConfig(kind="dynamic", delta=0.1) for _ in range(3)]
    Xs, Ys = zip(*(separable_stream(T=40, m=M, d=D, seed=s) for s in range(3)))
    sw = engine.sweep(lcfg, grid, np.stack(Xs), np.stack(Ys))
    for i in range(3):
        solo = engine.run(lcfg, grid[i], Xs[i], Ys[i])
        np.testing.assert_array_equal(solo.cumulative_bytes,
                                      sw[i].cumulative_bytes)
        np.testing.assert_allclose(solo.cumulative_loss,
                                   sw[i].cumulative_loss,
                                   rtol=1e-5, atol=1e-4)
    # seeds differ, so the runs must actually differ
    assert not np.array_equal(sw[0].cumulative_loss, sw[1].cumulative_loss)


def test_sweep_validates_inputs():
    lcfg = _kernel_cfg()
    with pytest.raises(ValueError):
        engine.sweep(lcfg, [], *susy_stream(T=10, m=M, d=D, seed=0))
    X, Y = susy_stream(T=10, m=M, d=D, seed=0)
    with pytest.raises(ValueError):
        engine.sweep(lcfg, [ProtocolConfig(kind="dynamic")],
                     np.stack([X, X]), np.stack([Y, Y]))


def test_assemble_sim_result_ignores_memory_order():
    """The host-side learner sum gives one answer whether the (T, m)
    series arrives row-major (the serving path stacks rows) or
    column-major (the TPU returns the scan's outputs that way); numpy's
    own summation order follows memory order."""
    from repro.core.substrate import substrate_of

    sub = substrate_of(_kernel_cfg())
    rng = np.random.default_rng(0)
    loss = rng.random((200, 32)).astype(np.float32)
    err = (rng.random((200, 32)) < 0.3).astype(np.float32)
    rest = (np.zeros(200, np.int64), np.zeros(200, np.float32),
            np.zeros(200, bool), np.zeros(200, np.float32))
    assert not np.array_equal(loss.sum(axis=1),
                              np.asfortranarray(loss).sum(axis=1))
    c = engine.assemble_sim_result(sub, False, loss, err, *rest)
    f = engine.assemble_sim_result(sub, False, np.asfortranarray(loss),
                                   np.asfortranarray(err), *rest)
    np.testing.assert_array_equal(c.cumulative_loss, f.cumulative_loss)
    np.testing.assert_array_equal(c.cumulative_errors, f.cumulative_errors)
