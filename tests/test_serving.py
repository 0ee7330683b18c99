"""Serving parity suite (DESIGN.md Sec. 10).

The contract under test: the same (T, m, d) labeled stream pushed
through :class:`repro.serving.KernelServingEngine` — with predict
query traffic riding along — reproduces ``engine.run`` BIT-FOR-BIT on
losses / errors and integer-exactly on the Sec. 3 byte ledger, for
{dynamic, periodic} x {SV, RFF, linear}; and a padded-batch
``Substrate.predict_batch`` call answers every request with exactly
the floats a per-request ``predict_one`` would have produced
(micro-batching is free, numerically).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, engine, simulation
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rff import RFFSpec
from repro.core.rkhs import KernelSpec
from repro.core.substrate import SVSubstrate, substrate_of
from repro.data import susy_stream
from repro.runtime import SystemConfig
from repro.runtime.clock import Clock
from repro.serving import (DEFAULT_BUCKETS, KernelServingEngine,
                           TickScheduler, make_arrivals, serve_stream)

T, M, D = 40, 4, 6


def _kcfg(budget=12):
    return LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                         budget=budget,
                         kernel=KernelSpec("gaussian", gamma=0.3), dim=D)


def _lcfg():
    return LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1, lam=0.001,
                         dim=D)


def _rspec():
    return RFFSpec(dim=D, num_features=32, gamma=0.3, seed=0)


def _stream(seed=1):
    return susy_stream(T=T, m=M, d=D, seed=seed)


def _assert_protocol_identical(res_ref, res_srv, tag):
    for field in ("cumulative_loss", "cumulative_errors",
                  "cumulative_bytes", "sync_rounds", "eps_history"):
        a, b = getattr(res_ref, field), getattr(res_srv, field)
        assert np.array_equal(a, b), (tag, field, a, b)
    assert res_ref.num_syncs == res_srv.num_syncs, tag
    assert res_ref.total_bytes == res_srv.total_bytes, tag


# ---------------------------------------------------------------------------
# Parity: serving path vs scan engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("learner_name", ["sv", "rff", "linear"])
@pytest.mark.parametrize("pcfg", [ProtocolConfig(kind="dynamic", delta=1.0),
                                  ProtocolConfig(kind="periodic", period=7)],
                         ids=["dynamic", "periodic"])
def test_serving_matches_engine(learner_name, pcfg):
    learner = {"sv": _kcfg(), "rff": _rspec(), "linear": _lcfg()}[learner_name]
    X, Y = _stream()
    res_ref = engine.run(learner, pcfg, X, Y)
    res_srv = serve_stream(learner, pcfg, X, Y, queries_per_round=2.0)
    assert res_ref.num_syncs > 0, "degenerate stream: no syncs to compare"
    _assert_protocol_identical(res_ref, res_srv.sim,
                               f"{learner_name}/{pcfg.kind}")
    # every feedback round was applied; queries were all answered
    assert res_srv.rounds == T
    assert res_srv.num_requests == 2 * T
    assert np.isfinite(res_srv.latencies).all()


def test_serving_query_rate_does_not_perturb_protocol():
    """Predict traffic reads model state and never touches it: any
    query rate leaves the protocol view bit-identical."""
    X, Y = _stream(seed=3)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    quiet = serve_stream(_kcfg(), pcfg, X, Y, queries_per_round=0.0)
    busy = serve_stream(_kcfg(), pcfg, X, Y, queries_per_round=5.0)
    _assert_protocol_identical(quiet.sim, busy.sim, "query-rate")
    assert quiet.num_requests == 0 and busy.num_requests == 5 * T


def test_serving_matches_engine_under_system_noise():
    """Stragglers and jitter reshuffle arrival *times*, never the
    per-learner stream order — the protocol view is timing-independent
    (the serving analogue of the async zero-latency collapse)."""
    X, Y = _stream(seed=4)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    res_ref = engine.run(_kcfg(), pcfg, X, Y)
    res_srv = serve_stream(
        _kcfg(), pcfg, X, Y, queries_per_round=1.0,
        sys_cfg=SystemConfig(seed=7, compute_jitter=0.4, straggler_frac=0.25,
                             straggler_mult=4.0, straggler_prob=0.5,
                             base_latency=0.3, latency_jitter=0.2,
                             bandwidth=1e5))
    _assert_protocol_identical(res_ref, res_srv.sim, "noisy-system")
    # metered sync network time exists on the noisy timeline
    assert len(res_srv.sync_delays) == res_srv.num_syncs
    assert (res_srv.sync_delays > 0).all()


def test_serve_stream_deterministic_under_seed():
    X, Y = _stream(seed=5)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    kw = dict(queries_per_round=3.0, query_seed=11,
              sys_cfg=SystemConfig(seed=2, compute_jitter=0.3,
                                   base_latency=0.1, bandwidth=1e6))
    a = serve_stream(_rspec(), pcfg, X, Y, **kw)
    b = serve_stream(_rspec(), pcfg, X, Y, **kw)
    assert np.array_equal(a.latencies, b.latencies)
    assert np.array_equal(a.queue_depth, b.queue_depth)
    assert np.array_equal(a.sim.cumulative_loss, b.sim.cumulative_loss)
    assert a.wall_clock == b.wall_clock


# ---------------------------------------------------------------------------
# Micro-batching: padded-batch predict == per-request predict
# ---------------------------------------------------------------------------


def _trained_models(sub, X, Y):
    """Push the stream through the protocol step so predict runs
    against non-trivial models."""
    step = jax.jit(engine.make_protocol_step(sub, "dynamic"))
    params = engine.params_of(ProtocolConfig(kind="dynamic", delta=1.0))
    carry = engine.init_protocol_carry(sub, X.shape[1])
    for t in range(X.shape[0]):
        carry, _ = step(params, carry,
                        (jnp.asarray(X[t]), jnp.asarray(Y[t]),
                         jnp.asarray(t, jnp.int32)))
    return sub.models_of(carry[0])


@pytest.mark.parametrize("learner_name", ["sv", "rff", "linear"])
def test_predict_batch_bit_equals_per_request(learner_name):
    learner = {"sv": _kcfg(), "rff": _rspec(), "linear": _lcfg()}[learner_name]
    sub = substrate_of(learner)
    X, Y = _stream(seed=6)
    models = _trained_models(sub, X, Y)
    rng = np.random.default_rng(0)
    n = 13                                   # pads into the 16-bucket
    lids = rng.integers(0, M, n).astype(np.int32)
    Xb = np.asarray(X[rng.integers(0, T, n), rng.integers(0, M, n)],
                    np.float32)
    pad = 16 - n
    batched = np.asarray(sub.predict_batch(
        models,
        jnp.asarray(np.concatenate([lids, np.zeros(pad, np.int32)])),
        jnp.asarray(np.concatenate([Xb, np.zeros((pad, D), np.float32)]))))
    solo = np.asarray([
        np.asarray(sub.predict_one(
            jax.tree.map(lambda v: v[lids[i]], models), jnp.asarray(Xb[i])))
        for i in range(n)])
    assert np.array_equal(batched[:n], solo), (learner_name, batched[:n], solo)


def test_bucket_sizes_key_compile_cache():
    """The engine serves every queue depth from the static bucket set
    (padding up), so the number of predict executables is bounded by
    len(buckets), sweep-style."""
    X, Y = _stream(seed=7)
    pcfg = ProtocolConfig(kind="periodic", period=10)
    res = serve_stream(_lcfg(), pcfg, X, Y, queries_per_round=3.0,
                       buckets=(1, 4, 16))
    assert set(res.bucket_counts) <= {1, 4, 16}
    assert sum(res.bucket_counts.values()) >= 1
    # every request got answered exactly once
    assert res.num_requests == 3 * T
    assert int(res.queue_depth.sum()) == res.num_requests


# ---------------------------------------------------------------------------
# Engine mechanics
# ---------------------------------------------------------------------------


def test_engine_tick_latency_semantics():
    """A request waits for the next tick-grid point after its arrival;
    with predict_cost=0 its latency is exactly that queue wait.  An
    arrival landing exactly ON a grid point is served by that tick
    (arrival events sort before the tick at equal time — the clock's
    (time, seq) order)."""
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1),
                              M, tick_interval=1.0)
    r1 = eng.submit(np.zeros(D), learner=0, at=0.25)
    r2 = eng.submit(np.ones(D), learner=3, at=1.0)    # lands on the grid
    res = eng.serve()
    assert r1.done and r2.done
    assert r1.done_time == pytest.approx(1.0)
    assert r1.latency == pytest.approx(0.75)
    assert r2.done_time == pytest.approx(1.0)
    assert r2.latency == pytest.approx(0.0)
    assert res.ticks == 1
    # an untrained linear model answers 0 everywhere
    assert r1.yhat == 0.0


def test_engine_predict_cost_shifts_done_time():
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1),
                              M, tick_interval=1.0, predict_cost=0.5,
                              buckets=(1,))
    ra = eng.submit(np.zeros(D), learner=0, at=0.0)
    rb = eng.submit(np.zeros(D), learner=1, at=0.0)
    eng.serve()
    # two single-slot buckets served back-to-back within the tick
    assert ra.done_time == pytest.approx(1.5)
    assert rb.done_time == pytest.approx(2.0)


def test_predict_compute_is_a_single_resource():
    """The predict server is one simulated resource: a tick's batches
    start no earlier than the previous tick's finished, and every
    completion lands on the timeline (wall_clock >= every done_time)."""
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1),
                              M, tick_interval=1.0, predict_cost=0.6,
                              buckets=(1,))
    first = [eng.submit(np.zeros(D), learner=0, at=0.1) for _ in range(3)]
    late = eng.submit(np.zeros(D), learner=1, at=1.5)
    res = eng.serve()
    assert [r.done_time for r in first] == pytest.approx([1.6, 2.2, 2.8])
    # the 2.0 tick finds the server busy until 2.8; no double-booking
    assert late.done_time == pytest.approx(3.4)
    assert res.wall_clock == pytest.approx(3.4)
    assert res.wall_clock >= max(r.done_time for r in first + [late])


def test_engine_ingress_validation():
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1), M)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(D + 1), learner=0)        # wrong dim
    with pytest.raises(ValueError):
        eng.submit(np.zeros(D), learner=M)            # no such learner
    with pytest.raises(ValueError):
        eng.feedback(np.zeros(D), 1.0, learner=0, at=-1.0)  # in the past
    with pytest.raises(ValueError):
        KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                    delta=0.1), M,
                            tick_interval=0.0)
    with pytest.raises(ValueError):
        KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                    delta=0.1), M,
                            buckets=())


def test_partial_feedback_rounds_wait():
    """Protocol rounds are lockstep: nothing is applied until every
    learner's next example arrived (the parity-critical queueing)."""
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="continuous"), M)
    for i in range(M - 1):
        eng.feedback(np.ones(D), 1.0, learner=i, at=0.1)
    res_half = eng.serve()
    assert res_half.rounds == 0 and res_half.num_syncs == 0
    eng.feedback(np.ones(D), 1.0, learner=M - 1, at=eng.clock.now + 0.1)
    res = eng.serve()
    assert res.rounds == 1 and res.num_syncs == 1


# ---------------------------------------------------------------------------
# compress_method default unification (the satellite bugfix)
# ---------------------------------------------------------------------------


def test_compress_method_default_is_one_constant():
    assert compression.DEFAULT_METHOD == "truncate"
    assert SVSubstrate().compress_method == compression.DEFAULT_METHOD
    assert (simulation.run_kernel_simulation.__defaults__[-1]
            == compression.DEFAULT_METHOD)
    # None sentinel keeps a substrate's own (non-default) configuration
    sub = SVSubstrate(lcfg=_kcfg(), compress_method="project")
    assert substrate_of(sub, compress_method=None).compress_method == "project"
    assert substrate_of(sub).compress_method == "project"
    # ... while an explicit value overrides it
    assert (substrate_of(sub, compress_method="truncate").compress_method
            == "truncate")


def test_engine_run_none_sentinel_respects_substrate_method():
    """engine.run(sub) must not silently reset a configured
    compress_method back to the default."""
    X, Y = _stream(seed=8)
    pcfg = ProtocolConfig(kind="periodic", period=5)
    sub_p = SVSubstrate(lcfg=_kcfg(), compress_method="project")
    res_none = engine.run(sub_p, pcfg, X, Y)
    res_explicit = engine.run(_kcfg(), pcfg, X, Y,
                              compress_method="project")
    assert np.array_equal(res_none.cumulative_loss,
                          res_explicit.cumulative_loss)
    assert np.array_equal(res_none.eps_history, res_explicit.eps_history)
    # and projection genuinely differs from the truncation default
    res_trunc = engine.run(_kcfg(), pcfg, X, Y)
    assert not np.array_equal(res_none.eps_history, res_trunc.eps_history)


# ---------------------------------------------------------------------------
# Mesh routing (out-of-process: jax locks the device count at init —
# the established pattern of tests/test_engine_mesh.py)
# ---------------------------------------------------------------------------


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np

    from repro.core import engine
    from repro.core.learners import LearnerConfig
    from repro.core.protocol import ProtocolConfig
    from repro.core.rkhs import KernelSpec
    from repro.data import susy_stream
    from repro.launch.serve import make_kernel_serving_engine

    assert len(jax.devices()) == 8
    T, M, D = 30, 8, 6
    X, Y = susy_stream(T=T, m=M, d=D, seed=3)
    kcfg = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5, lam=0.01,
                         budget=12, kernel=KernelSpec("gaussian", gamma=0.3),
                         dim=D)
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)

    eng = make_kernel_serving_engine(kcfg, pcfg, M)
    assert eng.home_shard(0) == 0 and eng.home_shard(M - 1) == 7
    rng = np.random.default_rng(0)
    for t in range(T):
        for i in range(M):
            eng.feedback(X[t, i], Y[t, i], learner=i, at=float(t + 1))
    for k in range(40):
        lid = int(rng.integers(M))
        eng.submit(X[int(rng.integers(T)), lid], learner=lid,
                   at=float(rng.uniform(0, T)))
    res = eng.serve()

    res_ref = engine.run(kcfg, pcfg, X, Y)
    assert np.array_equal(res_ref.cumulative_loss, res.sim.cumulative_loss)
    assert np.array_equal(res_ref.cumulative_bytes, res.sim.cumulative_bytes)
    assert res.num_requests == 40
    assert np.isfinite(res.latencies).all()

    # devices=1 degrades to identity routing, same launch code
    eng1 = make_kernel_serving_engine(kcfg, pcfg, M, devices=1)
    assert eng1.home_shard(M - 1) == 0
    print("MESH_SERVING_OK")
""")


def test_mesh_routed_serving_matches_engine():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MESH_SERVING_OK" in out.stdout


# ---------------------------------------------------------------------------
# Continuous batching, admission control, multi-tenancy (DESIGN.md Sec. 13)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overload", ["none", "shed", "defer"])
@pytest.mark.parametrize("kind", ["poisson", "bursty", "diurnal"])
@pytest.mark.parametrize("policy", ["tick", "continuous"])
def test_parity_under_policy_arrival_overload(policy, kind, overload):
    """The acceptance matrix: losses bitwise-identical and Sec. 3
    bytes integer-exact vs engine.run under EVERY scheduling policy,
    arrival model and overload level — scheduling is a pure
    latency/throughput knob, structurally unable to touch the
    protocol view."""
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    X, Y = _stream()
    res_ref = engine.run(_lcfg(), pcfg, X, Y)
    kw = dict(policy=policy, slots=2, predict_cost=0.05,
              arrivals=make_arrivals(kind, rate=6.0, seed=3))
    if overload != "none":
        # cap capacity below the offered load (one lane, batch of two,
        # 0.5 per launch = 4 req/s < rate 6) so admission actually binds
        kw.update(max_queue=2, overload=overload, slots=1,
                  predict_cost=0.5, buckets=(1, 2))
    res = serve_stream(_lcfg(), pcfg, X, Y, **kw)
    _assert_protocol_identical(res_ref, res.sim,
                               (policy, kind, overload))
    if overload == "shed":
        # the bounded queue actually bound something at this rate
        assert res.num_shed > 0
        assert res.num_requests + res.num_shed > 0
    elif overload == "defer":
        assert res.num_shed == 0


@pytest.mark.parametrize("learner_name", ["sv", "rff"])
def test_parity_under_overload_kernel_substrates(learner_name):
    """Substrate spot-check of the same matrix: the kernel substrates
    keep the contract under continuous batching with a shedding
    queue."""
    learner = {"sv": _kcfg(), "rff": _rspec()}[learner_name]
    pcfg = ProtocolConfig(kind="dynamic", delta=1.0)
    X, Y = _stream()
    res_ref = engine.run(learner, pcfg, X, Y)
    res = serve_stream(
        learner, pcfg, X, Y, policy="continuous", slots=1,
        predict_cost=0.2, max_queue=2, overload="shed",
        arrivals=make_arrivals("bursty", rate=8.0, seed=1))
    _assert_protocol_identical(res_ref, res.sim, learner_name)
    assert res.num_shed > 0                   # overload actually hit


def test_tick_grid_integer_exact_at_large_times():
    """The tick grid is an integer index k: each tick time is ONE
    multiply k * tick_interval, so huge horizons with tiny intervals
    stay exactly on grid (the old float probe
    floor(now / interval + 1e-9) + 1 drifts at this scale and can
    even produce a tick in the past)."""
    sch = TickScheduler(clock=Clock(), predict_fn=None,
                        shard_of=lambda l: 0, n_shards=1, buckets=(1,),
                        predict_cost=0.0, tick_interval=1e-3)
    for now in [0.0, 1e-3, 0.9999999999, 123456.789, 1e9, 1e9 + 0.25e-3,
                1e12]:
        k = sch._next_grid_k(now)
        t = k * sch.tick_interval
        assert t > now, (now, k, t)
        assert (k - 1) * sch.tick_interval <= now, (now, k)
    # grid points are exact fixed points: the next tick after k*dt is
    # (k+1)*dt, never a repeat or a skip
    for k in [1, 1_000, 1_000_000_000, 10 ** 12]:
        assert sch._next_grid_k(k * 1e-3) == k + 1
    with pytest.raises(OverflowError):
        sch._next_grid_k(float("inf"))


def test_engine_serves_at_large_now_tiny_tick():
    """End-to-end regression: a request arriving at simulated time 1e9
    on a 1e-3 grid is served within a couple of grid intervals — the
    float-drift failure mode (negative delay / off-grid tick) cannot
    occur."""
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1),
                              M, tick_interval=1e-3)
    big = 1.0e9
    r = eng.submit(np.zeros(D), learner=0, at=big + 0.4e-3)
    res = eng.serve()
    assert r.done
    assert 0.0 <= r.latency <= 2e-3
    assert res.ticks == 1


def test_serve_result_empty_and_single_stats():
    """Latency summaries are NaN-free and well-defined on degenerate
    runs: zero served requests gives 0.0 everywhere, one request gives
    its own latency at every percentile."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    eng = KernelServingEngine(_lcfg(), pcfg, M)
    res = eng.serve()                          # nothing ever submitted
    assert res.num_requests == 0
    pct = res.latency_percentiles()
    assert pct == {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    assert res.mean_latency == 0.0 and res.max_latency == 0.0
    assert res.mean_queue_depth == 0.0
    summary = res.summary()
    assert all(np.isfinite(v) for v in summary.values()), summary

    eng2 = KernelServingEngine(_lcfg(), pcfg, M, tick_interval=1.0)
    r = eng2.submit(np.zeros(D), learner=0, at=0.25)
    res2 = eng2.serve()
    assert res2.num_requests == 1
    pct2 = res2.latency_percentiles()
    assert pct2["p50"] == pct2["p90"] == pct2["p99"] == \
        pytest.approx(r.latency)
    assert res2.mean_latency == res2.max_latency == \
        pytest.approx(r.latency)
    assert all(np.isfinite(v) for v in res2.summary().values())


def test_multi_tenant_parity_shared_engine():
    """Several protocol instances share one engine, clock and slot
    pool; each tenant's protocol view still reproduces its own
    engine.run bit-for-bit, and launched batches never mix tenants."""
    from repro.telemetry.trace import Tracer
    X, Y = _stream()
    pcfg_a = ProtocolConfig(kind="dynamic", delta=1.0)
    pcfg_b = ProtocolConfig(kind="periodic", period=3)
    ref_a = engine.run(_lcfg(), pcfg_a, X, Y)
    ref_b = engine.run(_lcfg(), pcfg_b, X, Y)

    tr = Tracer()
    eng = KernelServingEngine(_lcfg(), pcfg_a, M, policy="continuous",
                              slots=2, predict_cost=0.05, tracer=tr)
    tb = eng.add_tenant(_lcfg(), pcfg_b)
    assert eng.num_tenants == 2
    rng = np.random.default_rng(0)
    for t in range(T):
        at = float(t + 1)
        for i in range(M):
            eng.feedback(X[t, i], Y[t, i], learner=i, at=at, tenant=0)
            eng.feedback(X[t, i], Y[t, i], learner=i, at=at, tenant=tb)
        # interleaved query traffic against both tenants
        eng.submit(X[t, 0], learner=int(rng.integers(M)),
                   at=at + 0.1, tenant=0)
        eng.submit(X[t, 0], learner=int(rng.integers(M)),
                   at=at + 0.2, tenant=tb)
    eng.serve()
    res_a, res_b = eng.results()
    _assert_protocol_identical(ref_a, res_a.sim, "tenant0")
    _assert_protocol_identical(ref_b, res_b.sim, "tenant1")
    assert res_a.num_requests == res_b.num_requests == T
    # every launched batch belongs to exactly one tenant
    batches = [e for e in tr.events if e["ph"] == "X"
               and e["name"].startswith("predict/bucket")]
    assert batches and all(e["args"]["tenant"] in (0, tb)
                           for e in batches)


def test_add_tenant_validates_input_dim():
    eng = KernelServingEngine(_lcfg(), ProtocolConfig(kind="dynamic",
                                                      delta=0.1), M)
    bad = LearnerConfig(algo="linear_sgd", loss="hinge", eta=0.1,
                        lam=0.001, dim=D + 1)
    with pytest.raises(ValueError):
        eng.add_tenant(bad, ProtocolConfig(kind="dynamic", delta=0.1))


def test_continuous_launches_on_arrival_not_grid():
    """The continuous policy's whole point: an idle engine answers a
    lone request in exactly predict_cost — no grid wait."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    eng = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                              predict_cost=0.25, tick_interval=1.0)
    r = eng.submit(np.zeros(D), learner=0, at=0.3)
    res = eng.serve()
    assert r.done_time == pytest.approx(0.55)
    assert r.latency == pytest.approx(0.25)
    assert res.ticks == 0                      # no grid involved
    assert res.policy == "continuous"


def test_continuous_hold_coalesces_within_budget():
    """With a latency budget, an under-full launch waits for fill —
    but never past oldest.arrival + max_wait."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    # lone request: held the full budget, then served
    eng = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                              predict_cost=0.1, max_wait=0.3,
                              buckets=(4,))
    r = eng.submit(np.zeros(D), learner=0, at=1.0)
    eng.serve()
    assert r.done_time == pytest.approx(1.0 + 0.3 + 0.1)

    # a second arrival inside the hold window rides the same launch
    eng2 = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                               predict_cost=0.1, max_wait=0.3,
                               buckets=(4,))
    ra = eng2.submit(np.zeros(D), learner=0, at=1.0)
    rb = eng2.submit(np.zeros(D), learner=0, at=1.2)
    res2 = eng2.serve()
    assert res2.launches == 1                 # coalesced
    assert ra.done_time == rb.done_time == pytest.approx(1.4)

    # a full bucket launches immediately, budget or not
    eng3 = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                               predict_cost=0.1, max_wait=0.5,
                               buckets=(2,))
    rs = [eng3.submit(np.zeros(D), learner=0, at=1.0) for _ in range(2)]
    eng3.serve()
    assert all(r.done_time == pytest.approx(1.1) for r in rs)


def test_slots_bound_concurrent_launches():
    """slots=k is k-way in-flight batching on one shard: with two
    lanes, two same-shard launches overlap; with one, they serialize
    (the PR 5 single predict server)."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    for slots, dones in ((1, [1.0, 2.0]), (2, [1.0, 1.0])):
        eng = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                                  predict_cost=1.0, buckets=(1,),
                                  slots=slots)
        rs = [eng.submit(np.zeros(D), learner=0, at=0.0)
              for _ in range(2)]
        eng.serve()
        assert [r.done_time for r in rs] == pytest.approx(dones), slots


def test_admission_shed_refuses_and_marks():
    """Over the queue bound with overload='shed', a request is refused:
    marked shed, never served, excluded from the latency ledger."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    eng = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                              predict_cost=1.0, buckets=(1,),
                              max_queue=1, overload="shed")
    ra = eng.submit(np.zeros(D), learner=0, at=0.0)   # launches at once
    rb = eng.submit(np.zeros(D), learner=0, at=0.0)   # queued
    rc = eng.submit(np.zeros(D), learner=0, at=0.0)   # queue full: shed
    res = eng.serve()
    assert ra.done and rb.done
    assert rc.shed and not rc.done
    assert res.num_shed == 1
    assert res.num_requests == 2              # shed never enters stats


def test_admission_defer_retries_and_accrues_latency():
    """overload='defer' re-prices the arrival onto the event clock:
    the request eventually lands, and its latency counts from the
    ORIGINAL arrival — deferral is never free."""
    pcfg = ProtocolConfig(kind="dynamic", delta=0.1)
    eng = KernelServingEngine(_lcfg(), pcfg, M, policy="continuous",
                              predict_cost=1.0, buckets=(1,),
                              max_queue=1, overload="defer",
                              defer_interval=0.25)
    rs = [eng.submit(np.zeros(D), learner=0, at=0.0) for _ in range(3)]
    res = eng.serve()
    assert all(r.done for r in rs)            # nothing lost
    assert res.num_shed == 0
    assert res.num_deferred >= 1
    last = max(rs, key=lambda r: r.done_time)
    assert last.deferrals >= 1
    assert last.latency >= 2.0                # queued behind two launches
    assert res.num_requests == 3
