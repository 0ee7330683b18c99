"""Population layer: churn, partial participation, and the cohort-only
byte ledger, proven against the full-participation oracle (DESIGN.md
Sec. 15).

Four contracts:

1. ORACLE PARITY — a churn-free population (or an all-True
   ``participation`` override) reproduces ``engine.run`` BIT-FOR-BIT
   (losses, errors, bytes, sync decisions) for
   {dynamic, periodic} x {SV, RFF, linear}.
2. SET-ALGEBRA BYTES — the masked device ledger
   (``device_sync_bytes_kernel(mask=...)``,
   ``device_rejoin_bytes_kernel``) equals the pure-Python set-algebra
   oracle (``sync_bytes_kernel`` / ``kernel_payload_bytes`` over the
   cohort-filtered id lists), hypothesis-driven across masks including
   all-on, all-off and single-learner rounds; end-to-end, a primal
   run's byte column equals the closed-form Sec. 3 oracle priced from
   (mask, sync decisions) alone.
3. EMPTY COHORT — a zero-participant round divides nothing by zero,
   emits zero bytes and zero loss, and never synchronizes.
4. DETERMINISM — same spec => byte-identical masks, results and
   Chrome traces, in-process and across ``PYTHONHASHSEED`` subprocesses
   (fixed integer SeedSequence tags, the tests/test_arrivals.py
   contract).
"""
import hashlib
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accounting, engine
from repro.core.accounting import ByteModel
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rff import RFFSpec
from repro.core.rkhs import KernelSpec
from repro.core.substrate import substrate_of
from repro.data import separable_stream, susy_stream
from repro.population import (ALWAYS_ON, DEFAULT_MIX, PHONE, SLOW,
                              AvailabilityClass, PopulationSpec,
                              class_assignment, participation_masks,
                              rejoin_counts, run_population,
                              trace_population)
from repro.telemetry.monitor import monitor_population
from repro.telemetry.trace import Tracer

T, M, D = 40, 6, 6


def _sv_cfg(budget=8):
    return LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                         budget=budget,
                         kernel=KernelSpec("gaussian", gamma=0.3), dim=D)


LEARNERS = {
    "sv": _sv_cfg(),
    "rff": RFFSpec(dim=D, num_features=16, gamma=0.3, seed=0),
    "linear": LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                            lam=0.001, dim=D),
}

PROTOS = {
    "dynamic": ProtocolConfig(kind="dynamic", delta=1.0),
    "periodic": ProtocolConfig(kind="periodic", period=7),
}

FULL_SPEC = PopulationSpec(m_total=M, classes=((ALWAYS_ON, 1.0),))


def _stream(seed=3):
    return susy_stream(T=T, m=M, d=D, seed=seed)


def _assert_bit_identical(a, b, tag=""):
    for field in ("cumulative_loss", "cumulative_errors", "cumulative_bytes",
                  "sync_rounds", "divergences", "eps_history"):
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert x.tobytes() == y.tobytes(), (tag, field, x, y)
    assert a.num_syncs == b.num_syncs, tag
    assert a.total_bytes == b.total_bytes, tag


# ---------------------------------------------------------------------------
# 1. full participation == engine.run, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("proto", PROTOS)
@pytest.mark.parametrize("name", LEARNERS)
def test_full_participation_bitwise_identical(name, proto):
    """The acceptance gate: a churn-free population through the masked
    scan core reproduces the unmasked engine bit-for-bit."""
    X, Y = _stream()
    learner, pcfg = LEARNERS[name], PROTOS[proto]
    oracle = engine.run(learner, pcfg, X, Y, record_divergence=True)
    pres = run_population(FULL_SPEC, learner, pcfg, X, Y,
                          record_divergence=True)
    assert oracle.num_syncs > 0, "degenerate run proves nothing"
    assert pres.participation.all()
    assert pres.total_rejoins == 0
    _assert_bit_identical(oracle, pres.sim, f"{name}/{proto}")


def test_all_true_override_bitwise_identical():
    """The override path: an explicit all-True mask through a churny
    spec is still the oracle, bit for bit."""
    X, Y = _stream(seed=5)
    spec = PopulationSpec(m_total=M, seed=11)     # churny DEFAULT_MIX
    pcfg = PROTOS["dynamic"]
    lcfg = LEARNERS["linear"]
    oracle = engine.run(lcfg, pcfg, X, Y)
    pres = run_population(spec, lcfg, pcfg, X, Y,
                          participation=np.ones((T, M), bool))
    _assert_bit_identical(oracle, pres.sim, "override")


def test_partial_mask_actually_changes_the_run():
    X, Y = _stream(seed=5)
    pcfg = PROTOS["dynamic"]
    lcfg = LEARNERS["linear"]
    full = engine.run(lcfg, pcfg, X, Y)
    pres = run_population(PopulationSpec(m_total=M, sample_rate=0.6, seed=2),
                          lcfg, pcfg, X, Y)
    assert pres.mean_cohort < M
    assert not np.array_equal(np.asarray(full.cumulative_loss),
                              np.asarray(pres.sim.cumulative_loss))


# ---------------------------------------------------------------------------
# 2a. masked device ledger vs the pure-Python set-algebra oracle
# ---------------------------------------------------------------------------


def _random_ids(rng, m, tau, pool):
    """Random stacked sv_id array mixing empty slots, shared ids and
    fresh ids (the tests/test_engine.py generator)."""
    ids = np.full((m, tau), -1, np.int32)
    for i in range(m):
        n_active = int(rng.integers(0, tau + 1))
        chosen = []
        for _ in range(n_active):
            if pool and rng.random() < 0.6:
                chosen.append(int(rng.choice(pool)))
            else:
                fresh = int(rng.integers(0, 100_000))
                pool.append(fresh)
                chosen.append(fresh)
        slots = rng.permutation(tau)[:n_active]
        ids[i, slots] = chosen
    return ids


def _round_mask(rng, m, t):
    """Random cohort, with the edge shapes forced early: all-on,
    all-off, then a single-learner round."""
    if t == 0:
        return np.ones(m, bool)
    if t == 1:
        return np.zeros(m, bool)
    if t == 2:
        mask = np.zeros(m, bool)
        mask[int(rng.integers(0, m))] = True
        return mask
    return rng.random(m) < rng.random()


def _assert_masked_ledger_agrees(seed, m=4, tau=5, n_syncs=6):
    from repro.core import rkhs

    rng = np.random.default_rng(seed)
    bm = ByteModel(dim=5)
    dev = accounting.device_ledger_init(m * tau)
    known: set = set()
    pool: list = []
    for t in range(n_syncs):
        ids = _random_ids(rng, m, tau, pool)
        mask = _round_mask(rng, m, t)
        cohort = [ids[i] for i in np.where(mask)[0]]
        b_host, known = accounting.sync_bytes_kernel(bm, cohort, known)
        b_dev, dev = accounting.device_sync_bytes_kernel(
            bm, jnp.asarray(ids), dev, mask=jnp.asarray(mask))
        assert int(b_dev) == b_host, (t, mask, int(b_dev), b_host)
    known_dev = np.asarray(dev.known)
    known_dev = set(known_dev[known_dev < int(rkhs.ID_SENTINEL)].tolist())
    assert known_dev == known


@pytest.mark.parametrize("seed", range(6))
def test_masked_sync_bytes_match_set_oracle(seed):
    _assert_masked_ledger_agrees(seed)


def test_masked_sync_bytes_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def inner(seed):
        _assert_masked_ledger_agrees(seed, m=5, tau=4, n_syncs=4)

    inner()


def _assert_rejoin_bytes_agree(seed, m=5, tau=6):
    rng = np.random.default_rng(seed)
    bm = ByteModel(dim=4)
    pool: list = []
    ref = _random_ids(rng, 1, tau, pool)[0]          # reference id row
    ids = _random_ids(rng, m, tau, pool)
    for t in range(4):
        rejoin = _round_mask(rng, m, t)
        ref_set = set(ref[ref >= 0].tolist())
        want = sum(
            accounting.kernel_payload_bytes(
                bm, ref_set, set(ids[i][ids[i] >= 0].tolist()))
            for i in np.where(rejoin)[0])
        got = accounting.device_rejoin_bytes_kernel(
            bm, jnp.asarray(ref), jnp.asarray(ids), jnp.asarray(rejoin))
        assert int(got) == want, (t, rejoin, int(got), want)


@pytest.mark.parametrize("seed", range(4))
def test_rejoin_bytes_match_payload_oracle(seed):
    _assert_rejoin_bytes_agree(seed)


def test_rejoin_bytes_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def inner(seed):
        _assert_rejoin_bytes_agree(seed)

    inner()


# ---------------------------------------------------------------------------
# 2b. end-to-end primal byte column == closed-form Sec. 3 oracle
# ---------------------------------------------------------------------------


def _primal_oracle_bytes(res, mask, num_params, topology):
    """Per-round Sec. 3 bytes priced from (mask, sync decisions) alone:
    every rejoiner downloads |theta| B; a sync moves 2 c_t |theta| B
    (coordinator) or 2 (c_t - 1) |theta| B (ring total)."""
    Tn, _ = mask.shape
    sync_set = {int(t) for t in np.asarray(res.sync_rounds)}
    r = rejoin_counts(mask)
    c = mask.sum(axis=1).astype(np.int64)
    per = np.zeros(Tn, np.int64)
    for t in range(Tn):
        per[t] = int(r[t]) * num_params * 4
        if t in sync_set:
            if topology == "coordinator":
                per[t] += 2 * int(c[t]) * num_params * 4
            else:
                per[t] += 2 * max(int(c[t]) - 1, 0) * num_params * 4
    return np.cumsum(per)


@pytest.mark.parametrize("topology", ["coordinator", "allreduce"])
@pytest.mark.parametrize("name", ["linear", "rff"])
def test_primal_bytes_match_closed_form_oracle(name, topology):
    X, Y = _stream(seed=7)
    learner = LEARNERS[name]
    spec = PopulationSpec(m_total=M, sample_rate=0.7, seed=4)
    pres = run_population(spec, learner,
                          ProtocolConfig(kind="dynamic", delta=0.3), X, Y,
                          topology=topology)
    assert pres.sim.num_syncs > 0
    assert pres.total_rejoins > 0, "churn-free mask proves nothing"
    want = _primal_oracle_bytes(pres.sim, pres.participation,
                                substrate_of(learner).num_params, topology)
    np.testing.assert_array_equal(
        np.asarray(pres.sim.cumulative_bytes, np.int64), want)


def test_periodic_primal_bytes_match_closed_form_oracle():
    X, Y = _stream(seed=9)
    lcfg = LEARNERS["linear"]
    pres = run_population(PopulationSpec(m_total=M, seed=1), lcfg,
                          PROTOS["periodic"], X, Y)
    want = _primal_oracle_bytes(pres.sim, pres.participation,
                                substrate_of(lcfg).num_params, "coordinator")
    np.testing.assert_array_equal(
        np.asarray(pres.sim.cumulative_bytes, np.int64), want)


# ---------------------------------------------------------------------------
# 3. empty-cohort rounds
# ---------------------------------------------------------------------------


def _mask_with_empty_rounds():
    mask = np.ones((T, M), bool)
    mask[0] = True
    mask[5] = False                    # empty round mid-stream
    mask[6] = False                    # and a consecutive one
    mask[12, 1:] = False               # single-learner round
    mask[20:23, ::2] = False           # staggered churn
    return mask


@pytest.mark.parametrize("name", LEARNERS)
@pytest.mark.parametrize("proto", ["dynamic", "periodic", "continuous"])
def test_empty_cohort_rounds_are_inert(name, proto):
    """A zero-participant round must not divide by zero, emit phantom
    bytes, sync, or accrue loss — for every substrate and protocol."""
    X, Y = _stream(seed=2)
    pcfg = (ProtocolConfig(kind="continuous") if proto == "continuous"
            else PROTOS[proto])
    mask = _mask_with_empty_rounds()
    pres = run_population(PopulationSpec(m_total=M), LEARNERS[name], pcfg,
                          X, Y, participation=mask)
    loss = np.asarray(pres.sim.cumulative_loss, np.float64)
    nbytes = np.asarray(pres.sim.cumulative_bytes, np.int64)
    err = np.asarray(pres.sim.cumulative_errors, np.float64)
    assert np.isfinite(loss).all(), name
    for t in (5, 6):
        assert t not in set(int(s) for s in pres.sim.sync_rounds)
        assert loss[t] == loss[t - 1], (name, proto)
        assert err[t] == err[t - 1], (name, proto)
        # empty round: no sync, no rejoins (mask[6] has none) => 0 bytes
        if t == 6:
            assert nbytes[t] == nbytes[t - 1], (name, proto)


@pytest.mark.parametrize("name", LEARNERS)
def test_fully_idle_population(name):
    """Every round empty: zero loss, zero bytes, zero syncs — and the
    monitor trivially holds at cohort 1."""
    X, Y = _stream(seed=2)
    pres = run_population(PopulationSpec(m_total=M), LEARNERS[name],
                          PROTOS["dynamic"], X, Y,
                          participation=np.zeros((T, M), bool))
    assert pres.sim.total_bytes == 0
    assert pres.sim.num_syncs == 0
    assert float(pres.sim.total_loss) == 0.0
    assert np.isfinite(np.asarray(pres.sim.cumulative_loss)).all()
    mon = monitor_population(pres, LEARNERS[name])
    assert mon.ok and mon.m == 1


@pytest.mark.parametrize("name", LEARNERS)
def test_average_stacked_masked_empty_cohort_is_finite(name):
    """The division guard itself: averaging an empty cohort must not
    produce NaN (cnt is clamped before the divide)."""
    import jax

    sub = substrate_of(LEARNERS[name])
    models = sub.models_of(sub.init(M))
    avg, _ = sub.average_stacked_masked(models, jnp.zeros(M, bool))
    for leaf in jax.tree.leaves(avg):
        leaf = np.asarray(leaf)
        if leaf.dtype.kind == "f":
            assert np.isfinite(leaf).all(), name


# ---------------------------------------------------------------------------
# 4. determinism: masks, results, traces — in- and cross-process
# ---------------------------------------------------------------------------


def test_participation_masks_byte_identical_under_seed():
    spec = PopulationSpec(m_total=64, seed=7)
    a = participation_masks(spec, 20)
    b = participation_masks(spec, 20)
    assert a.tobytes() == b.tobytes()
    other = participation_masks(
        PopulationSpec(m_total=64, seed=8), 20)
    assert other.tobytes() != a.tobytes()


def test_population_run_and_trace_byte_identical_under_seed():
    X, Y = _stream(seed=5)
    spec = PopulationSpec(m_total=M, sample_rate=0.8, seed=3)

    def go():
        pres = run_population(spec, LEARNERS["linear"], PROTOS["dynamic"],
                              X, Y)
        tr = Tracer()
        trace_population(pres, tr)
        mon = monitor_population(pres, LEARNERS["linear"])
        mon.emit(tr)
        return pres, tr.to_json()

    p1, j1 = go()
    p2, j2 = go()
    _assert_bit_identical(p1.sim, p2.sim, "rerun")
    assert p1.participation.tobytes() == p2.participation.tobytes()
    assert j1 == j2
    p3 = run_population(
        PopulationSpec(m_total=M, sample_rate=0.8, seed=4),
        LEARNERS["linear"], PROTOS["dynamic"], X, Y)
    assert p3.participation.tobytes() != p1.participation.tobytes()


def test_population_deterministic_across_processes():
    """PYTHONHASHSEED must not reach any population draw: a fresh
    interpreter reproduces masks AND the full run byte-for-byte."""
    X, Y = _stream(seed=5)
    spec = PopulationSpec(m_total=M, sample_rate=0.8, seed=3)
    pres = run_population(spec, LEARNERS["linear"], PROTOS["dynamic"], X, Y)
    d_mask = hashlib.sha256(pres.participation.tobytes()).hexdigest()
    d_bytes = hashlib.sha256(
        np.asarray(pres.sim.cumulative_bytes, np.int64).tobytes()).hexdigest()
    d_loss = hashlib.sha256(
        np.asarray(pres.sim.cumulative_loss).tobytes()).hexdigest()
    script = textwrap.dedent(f"""
        import hashlib
        import numpy as np
        from repro.core.learners import LearnerConfig
        from repro.core.protocol import ProtocolConfig
        from repro.data import susy_stream
        from repro.population import PopulationSpec, run_population
        X, Y = susy_stream(T={T}, m={M}, d={D}, seed=5)
        spec = PopulationSpec(m_total={M}, sample_rate=0.8, seed=3)
        lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                             lam=0.001, dim={D})
        pres = run_population(spec, lcfg,
                              ProtocolConfig(kind="dynamic", delta=1.0), X, Y)
        print("mask", hashlib.sha256(
            pres.participation.tobytes()).hexdigest())
        print("bytes", hashlib.sha256(np.asarray(
            pres.sim.cumulative_bytes, np.int64).tobytes()).hexdigest())
        print("loss", hashlib.sha256(np.asarray(
            pres.sim.cumulative_loss).tobytes()).hexdigest())
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    env["PYTHONHASHSEED"] = "99"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    got = dict(line.split() for line in out.stdout.strip().splitlines())
    assert got["mask"] == d_mask
    assert got["bytes"] == d_bytes
    assert got["loss"] == d_loss


# ---------------------------------------------------------------------------
# availability-model unit contracts
# ---------------------------------------------------------------------------


def test_class_assignment_exact_histogram():
    spec = PopulationSpec(m_total=103, seed=0)     # fractions don't divide
    ids = class_assignment(spec)
    assert ids.shape == (103,)
    counts = np.bincount(ids, minlength=len(spec.classes))
    assert counts.sum() == 103
    # largest-remainder: every class within 1 of its exact share
    for k, (_, frac) in enumerate(spec.classes):
        assert abs(counts[k] - frac * 103) < 1.0 + 1e-9
    # deterministic
    assert np.array_equal(ids, class_assignment(spec))


def test_stationary_on_and_validation():
    assert ALWAYS_ON.stationary_on == 1.0
    assert PHONE.stationary_on == pytest.approx(0.35 / 0.50)
    assert SLOW.speed == 0.5
    with pytest.raises(ValueError):
        AvailabilityClass("bad", p_drop=1.5)
    with pytest.raises(ValueError):
        PopulationSpec(m_total=0)
    with pytest.raises(ValueError):
        PopulationSpec(m_total=4, sample_rate=0.0)
    with pytest.raises(ValueError):
        PopulationSpec(m_total=4, classes=((ALWAYS_ON, 0.5),))
    with pytest.raises(ValueError):
        participation_masks(PopulationSpec(m_total=4), 0)


def test_rejoin_counts_convention():
    mask = np.asarray([[1, 0, 0],
                       [1, 1, 0],      # learner 1 rejoins
                       [0, 1, 1],      # learner 2 rejoins
                       [1, 1, 1]],     # learner 0 rejoins
                      bool)
    np.testing.assert_array_equal(rejoin_counts(mask), [0, 1, 1, 1])


def test_churn_rates_track_the_class_mix():
    """Statistical sanity on a big deterministic draw: the realized
    on-fraction of each class sits near stationary_on * speed."""
    spec = PopulationSpec(m_total=4000, seed=0)
    mask = participation_masks(spec, 50)
    ids = class_assignment(spec)
    for k, (cls, _) in enumerate(spec.classes):
        realized = mask[10:, ids == k].mean()      # past burn-in
        expect = cls.stationary_on * cls.speed
        assert abs(realized - expect) < 0.05, (cls.name, realized, expect)


def test_run_population_validates_shapes():
    X, Y = separable_stream(T=5, m=3, d=4, seed=0)
    lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", dim=4)
    with pytest.raises(ValueError, match="m_total"):
        run_population(PopulationSpec(m_total=7), lcfg, PROTOS["dynamic"],
                       X, Y)
    with pytest.raises(ValueError, match="participation"):
        run_population(PopulationSpec(m_total=3), lcfg, PROTOS["dynamic"],
                       X, Y, participation=np.ones((4, 3), bool))
    with pytest.raises(ValueError, match="participation"):
        engine.run(lcfg, PROTOS["dynamic"], X, Y,
                   participation=np.ones((5, 2), bool))


# ---------------------------------------------------------------------------
# monitor: Def. 1 priced at the largest cohort, integer-exact bytes
# ---------------------------------------------------------------------------


def test_monitor_population_integer_exact_and_cohort_priced():
    X, Y = _stream(seed=5)
    spec = PopulationSpec(m_total=M, sample_rate=0.8, seed=3)
    pres = run_population(spec, LEARNERS["linear"], PROTOS["dynamic"], X, Y)
    mon = monitor_population(pres, LEARNERS["linear"])
    assert mon.m == int(pres.cohort_sizes.max())
    series = mon.series()
    np.testing.assert_array_equal(
        series.cumulative_bytes,
        np.asarray(pres.sim.cumulative_bytes, np.int64))
    assert series.cumulative_loss.tobytes() == np.asarray(
        pres.sim.cumulative_loss, np.float64).tobytes()
    assert mon.ok


# ---------------------------------------------------------------------------
# mesh: masked runs shard like unmasked ones (subprocess, 8 devices)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np

    from repro.core import engine
    from repro.core.learners import LearnerConfig
    from repro.core.protocol import ProtocolConfig
    from repro.core.rff import RFFSpec
    from repro.core.rkhs import KernelSpec
    from repro.data import susy_stream
    from repro.launch.mesh import make_learner_mesh
    from repro.population import PopulationSpec, run_population

    assert len(jax.devices()) == 8
    mesh = make_learner_mesh()
    T, M, D = 30, 8, 6
    X, Y = susy_stream(T=T, m=M, d=D, seed=3)
    spec = PopulationSpec(m_total=M, sample_rate=0.8, seed=5)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)

    learners = [
        ("sv", LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5,
                             lam=0.01, budget=8,
                             kernel=KernelSpec("gaussian", gamma=0.3), dim=D)),
        ("rff", RFFSpec(dim=D, num_features=16, gamma=0.3, seed=0)),
        ("linear", LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                                 lam=0.001, dim=D)),
    ]
    for name, learner in learners:
        p1 = run_population(spec, learner, pcfg, X, Y)
        p8 = run_population(spec, learner, pcfg, X, Y, mesh=mesh)
        assert p1.total_rejoins > 0, name
        for field in ("cumulative_loss", "cumulative_errors",
                      "cumulative_bytes", "sync_rounds"):
            a = np.asarray(getattr(p1.sim, field))
            b = np.asarray(getattr(p8.sim, field))
            assert a.tobytes() == b.tobytes(), (name, field)
        assert p1.sim.total_bytes == p8.sim.total_bytes, name
    print("OK population mesh parity")
""")


@pytest.mark.slow
def test_masked_engine_matches_single_device_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK population mesh parity" in r.stdout
