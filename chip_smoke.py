"""Bring-up smoke: the kernel learners' main path on a TPU.

  python chip_smoke.py              # one chip: phases (a)-(c)
  python chip_smoke.py --chips 4    # four chips: the learner-mesh engine only

One deployment serves every phase: the paper's SUSY layout
(``susy_stream``, d=8, Gaussian kernel gamma=0.3, hinge loss, eta=0.5,
lambda=0.01), m=32 learners, T=1000 rounds, data drawn from ``--seed``.

(a) engine, SV: ``engine.run`` at SV budget 512 on the fused Pallas
    kernels, dynamic (Delta=1) and periodic (period 10), checked against
    ``backend="reference"`` on the same chip: the kernels' outputs on
    the run's final models agree within the pinned parity tolerance,
    and sync rounds and bytes are identical up to the first round in
    which an insertion or sync decision differs;
(b) engine, RFF: 1024 random Fourier features on the fused ``rff_step``
    kernel, dynamic, checked against the reference backend the same way;
(c) serving: ``serve_stream`` over (a)'s substrate with Poisson predict
    traffic, the continuous batching policy and 200 feedback rounds;
    every request is answered from the fused bucket path and the
    protocol view is bit-identical to ``engine.run``.

``--chips 4`` runs ``engine.run(..., mesh=make_learner_mesh(4))`` for SV
(budget 512, Pallas), RFF and linear learners under both protocols, and
the single-device engine on the same host as the comparison: bytes and
sync rounds integer-exact, losses bitwise.

The script exits non-zero, and prints no result line, unless JAX's
default backend is a TPU (there is no CPU or interpret fallback) and
every check passes.  ``ops.TRACE_COUNTS`` proves that the fused kernels
produced the numbers.  Times printed here are one smoke run's wall
times, compilation included where it says so, not benchmark results.
The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

T, M, D = 1000, 32, 8          # rounds, learners, SUSY low-level features
GAMMA, ETA, LAM = 0.3, 0.5, 0.01
BUDGET = 512                   # SV budget: the fused kernels engage from 128
RFF_FEATURES = 1024
DELTA, PERIOD = 1.0, 10
SERVE_ROUNDS = 200
QUERY_RATE = 2.0               # Poisson predicts per simulated time unit
PREDICT_COST = 0.5             # simulated predict time: lets batches form
MESH_CHIPS = 4


def log(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"  ok: {what}")


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# The deployment
# ---------------------------------------------------------------------------


def deployment(seed: int):
    from repro.core.learners import LearnerConfig
    from repro.core.protocol import ProtocolConfig
    from repro.core.rff import RFFSpec
    from repro.core.rkhs import KernelSpec
    from repro.core.substrate import LinearSubstrate, RFFSubstrate, SVSubstrate
    from repro.data import susy_stream

    X, Y = susy_stream(T=T, m=M, d=D, seed=seed)
    kcfg = LearnerConfig(algo="kernel_sgd", loss="hinge", eta=ETA, lam=LAM,
                         budget=BUDGET, kernel=KernelSpec("gaussian",
                                                          gamma=GAMMA),
                         dim=D)
    rspec = RFFSpec(dim=D, num_features=RFF_FEATURES, gamma=GAMMA, seed=seed)
    lcfg = LearnerConfig(algo="linear_sgd", loss="hinge", eta=ETA, lam=LAM,
                         dim=D)
    return dict(
        X=X, Y=Y,
        protos={"dynamic": ProtocolConfig(kind="dynamic", delta=DELTA),
                "periodic": ProtocolConfig(kind="periodic", period=PERIOD)},
        sv={b: SVSubstrate(lcfg=kcfg, backend=b)
            for b in ("pallas", "reference")},
        rff={b: RFFSubstrate(spec=rspec, eta=ETA, lam=LAM, backend=b)
             for b in ("pallas", "reference")},
        linear=LinearSubstrate(lcfg=lcfg),
    )


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def replay(sub, pcfg, X, Y):
    """The scan engine's own step (``engine.make_protocol_step``) scanned
    over the stream, for what ``engine.run`` does not return: the
    per-learner (T, m) losses and the final carry."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core import engine

    step = engine.make_protocol_step(sub, pcfg.kind)

    @jax.jit
    def scan(params, X, Y):
        carry0 = engine.init_protocol_carry(sub, X.shape[1])
        ts = jnp.arange(X.shape[0], dtype=jnp.int32)
        return lax.scan(functools.partial(step, params), carry0, (X, Y, ts))

    carry, outs = scan(engine.params_of(pcfg), jnp.asarray(X), jnp.asarray(Y))
    loss, _, nbytes, _, flags, _ = (np.asarray(o) for o in outs)
    return carry, loss, nbytes, flags


def round_series(res):
    """(per-round bytes, per-round sync flags) of a SimResult."""
    nbytes = np.diff(res.cumulative_bytes, prepend=0)
    flags = np.zeros(len(res.cumulative_bytes), bool)
    flags[res.sync_rounds] = True
    return nbytes, flags


def first_true(mask) -> int | None:
    rows = np.nonzero(mask)[0]
    return int(rows[0]) if len(rows) else None


SIM_FIELDS = ("cumulative_loss", "cumulative_errors", "cumulative_bytes",
              "sync_rounds")


def same_sim(a, b) -> bool:
    """Protocol views bit-identical; logs the first difference if not."""
    for field in SIM_FIELDS:
        x, y = getattr(a, field), getattr(b, field)
        if not np.array_equal(x, y):
            n = min(len(x), len(y))
            log(f"  {field} differs: first at index "
                f"{first_true(x[:n] != y[:n])} (lengths {len(x)}, {len(y)})")
            return False
    return True


def max_ulp(a, b) -> int:
    """Largest distance, in float32 units in the last place, between the
    per-round summed losses of two SimResults."""
    ra = np.diff(a.cumulative_loss, prepend=0.0).astype(np.float32)
    rb = np.diff(b.cumulative_loss, prepend=0.0).astype(np.float32)
    return int(np.max(np.abs(ra.view(np.int32).astype(np.int64)
                             - rb.view(np.int32).astype(np.int64))))


def run_twice(sub, pcfg, X, Y, **kw):
    """engine.run once cold (compilation included) and once warm; the
    two must agree bitwise (the engine is deterministic under seed)."""
    from repro.core import engine

    r1, t_first = timed(engine.run, sub, pcfg, X, Y, **kw)
    r2, t_steady = timed(engine.run, sub, pcfg, X, Y, **kw)
    log(f"  engine.run: first call {t_first:.3f} s (compile included), "
        f"second call {t_steady:.3f} s; syncs={r2.num_syncs} "
        f"bytes={r2.total_bytes} loss={r2.total_loss:.6f}")
    check(same_sim(r1, r2), "two runs of the same config are bit-identical")
    return r2


def compare_decisions(name, r_p, r_r, ins_p=None, ins_r=None):
    """Sync rounds and bytes of the pallas and reference runs are
    identical before the first round in which an insertion (a learner
    with positive hinge loss) or a sync decision differs."""
    b_p, f_p = round_series(r_p)
    b_r, f_r = round_series(r_r)
    differs = f_p != f_r
    if ins_p is not None:
        differs = differs | (ins_p != ins_r).any(axis=1)
    first = first_true(differs)
    log(f"  {name}: first round with a differing insertion or sync "
        f"decision: {first if first is not None else 'none'} of {len(f_p)}")
    upto = len(f_p) if first is None else first
    check(np.array_equal(f_p[:upto], f_r[:upto])
          and np.array_equal(b_p[:upto], b_r[:upto]),
          f"{name}: sync rounds and bytes identical to the reference "
          f"backend over rounds [0, {upto})")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_engine_sv(dep):
    import jax

    from repro.core import engine
    from repro.kernels import ops

    X, Y = dep["X"], dep["Y"]
    sv_p, sv_r = dep["sv"]["pallas"], dep["sv"]["reference"]
    finals = {}
    for kind, pcfg in dep["protos"].items():
        log(f"(a) engine, SV budget {BUDGET}, {kind}")
        ops.reset_trace_counts()
        r_p = run_twice(sv_p, pcfg, X, Y)
        counts = dict(ops.TRACE_COUNTS)
        log(f"  trace counts (pallas run): {counts}")
        check(counts.get("sv_predict", 0) > 0, "sv_predict launched")
        if kind == "dynamic":
            check(counts.get("quadform", 0) > 0, "quadform launched")
        r_r, t = timed(engine.run, sv_r, pcfg, X, Y)
        log(f"  reference backend: {t:.3f} s (compile included); "
            f"syncs={r_r.num_syncs} bytes={r_r.total_bytes} "
            f"loss={r_r.total_loss:.6f}")

        (carry, loss_p, nb_p, fl_p), t = timed(replay, sv_p, pcfg, X, Y)
        _, loss_r, nb_r, fl_r = replay(sv_r, pcfg, X, Y)
        log(f"  step replays: {t:.3f} s for the pallas one "
            f"(compile included)")
        for name, (nb, fl), res in (("pallas", (nb_p, fl_p), r_p),
                                    ("reference", (nb_r, fl_r), r_r)):
            b, f = round_series(res)
            check(np.array_equal(nb, b) and np.array_equal(fl, f),
                  f"{name}: the step replay's bytes and syncs equal "
                  "engine.run's")
        compare_decisions(f"SV {kind}", r_p, r_r, loss_p > 0, loss_r > 0)

        finals[kind] = carry

    # the kernels against the reference expressions on the pallas runs'
    # final models.  A periodic run ends on a sync (T % period == 0), so
    # its learners all equal its reference; the distances are taken from
    # the dynamic run's learners to the periodic run's reference.
    models = {k: sv_p.models_of(c[0]) for k, c in finals.items()}
    ref = finals["periodic"][1]
    xq = jax.numpy.asarray(X[-1])
    ops.reset_trace_counts()
    got = {}
    for name, sub in (("pallas", sv_p), ("reference", sv_r)):
        predict, dist = jax.jit(sub.predict), jax.jit(sub.dist_to_ref)
        got[name] = {
            "sv_predict, dynamic run": predict(models["dynamic"], xq),
            "sv_predict, periodic run": predict(models["periodic"], xq),
            "dist_to_ref (quadform)": dist(models["dynamic"], ref)}
    check(ops.TRACE_COUNTS["sv_predict"] > 0
          and ops.TRACE_COUNTS["quadform"] > 0,
          "final-model checks ran sv_predict and quadform")
    for what, b in got["reference"].items():
        a, b = np.asarray(got["pallas"][what]), np.asarray(b)
        log(f"  {what} on the final models: max |pallas - reference| "
            f"= {float(np.max(np.abs(a - b))):.3e} "
            f"(values up to {float(np.max(np.abs(b))):.3e})")
        check(np.all(np.isfinite(a)) and float(np.max(np.abs(b))) > 0
              and np.allclose(a, b, rtol=ops.PARITY_RTOL,
                              atol=ops.PARITY_ATOL),
              f"{what}: non-zero, within the pinned parity tolerance "
              f"(rtol={ops.PARITY_RTOL}, atol={ops.PARITY_ATOL})")


def phase_engine_rff(dep):
    from repro.core import engine
    from repro.kernels import ops

    X, Y = dep["X"], dep["Y"]
    pcfg = dep["protos"]["dynamic"]
    log(f"(b) engine, RFF {RFF_FEATURES} features, dynamic")
    ops.reset_trace_counts()
    r_p = run_twice(dep["rff"]["pallas"], pcfg, X, Y)
    counts = dict(ops.TRACE_COUNTS)
    log(f"  trace counts (pallas run): {counts}")
    check(counts.get("rff_step", 0) > 0, "rff_step launched")
    check(bool(np.all(np.isfinite(r_p.cumulative_loss))), "losses finite")
    r_r, t = timed(engine.run, dep["rff"]["reference"], pcfg, X, Y)
    log(f"  reference backend: {t:.3f} s (compile included); "
        f"syncs={r_r.num_syncs} bytes={r_r.total_bytes} "
        f"loss={r_r.total_loss:.6f}")
    compare_decisions("RFF dynamic", r_p, r_r)


def phase_serving(dep, seed: int):
    from repro.core import engine
    from repro.kernels import ops
    from repro.runtime import SystemConfig, SystemModel
    from repro.serving import PoissonArrivals, serve_stream

    X, Y = dep["X"][:SERVE_ROUNDS], dep["Y"][:SERVE_ROUNDS]
    sub = dep["sv"]["pallas"]
    pcfg = dep["protos"]["dynamic"]
    log(f"(c) serving, SV budget {BUDGET}, dynamic, {SERVE_ROUNDS} feedback "
        f"rounds, Poisson predicts at {QUERY_RATE}/unit, continuous policy")
    arrivals = PoissonArrivals(rate=QUERY_RATE, seed=seed)
    # serve_stream's horizon: the last feedback arrival on the seeded
    # compute timeline of a default SystemConfig
    horizon = float(np.max(np.cumsum(
        SystemModel(SystemConfig(), M).draw_compute(SERVE_ROUNDS), axis=0)))
    offered = len(arrivals.times(horizon))
    ops.reset_trace_counts()
    res, t = timed(serve_stream, sub, pcfg, X, Y, arrivals=arrivals,
                   query_seed=seed, policy="continuous", slots=1,
                   predict_cost=PREDICT_COST, slo=4 * PREDICT_COST)
    n_pred = ops.TRACE_COUNTS["sv_predict"]
    log(f"  serve_stream: {t:.3f} s wall (compilation included); "
        f"{res.num_requests} requests, {res.rounds} rounds, "
        f"{res.launches} predict launches, buckets "
        f"{dict(sorted(res.bucket_counts.items()))}")
    log(f"  trace counts: {dict(ops.TRACE_COUNTS)}")
    check(res.num_requests == offered and res.num_shed == 0
          and bool(np.all(np.isfinite(res.latencies))),
          f"all {offered} offered predict requests answered")
    check(res.rounds == SERVE_ROUNDS, f"{SERVE_ROUNDS} feedback rounds "
          "applied")
    # one trace of the protocol step plus one per bucket shape
    check(n_pred == 1 + len(res.bucket_counts),
          f"sv_predict traced for the protocol step and for each of the "
          f"{len(res.bucket_counts)} bucket shapes ({n_pred} launches)")
    r, t = timed(engine.run, sub, pcfg, X, Y)
    log(f"  engine.run on the same stream: {t:.3f} s (compile included)")
    check(same_sim(res.sim, r), "serving's protocol view is bit-identical "
          "to engine.run (losses, errors, bytes, sync rounds)")


def phase_mesh(dep):
    from repro.core import engine
    from repro.kernels import ops
    from repro.launch.mesh import make_learner_mesh

    X, Y = dep["X"], dep["Y"]
    mesh = make_learner_mesh(MESH_CHIPS)
    log(f"mesh: {MESH_CHIPS} devices on axis 'learners', "
        f"{M // MESH_CHIPS} learners per device")
    subs = (("SV", dep["sv"]["pallas"]), ("RFF", dep["rff"]["pallas"]),
            ("linear", dep["linear"]))
    failed = []
    for name, sub in subs:
        for kind, pcfg in dep["protos"].items():
            log(f"{name} {kind}")
            ops.reset_trace_counts()
            r1, t1 = timed(engine.run, sub, pcfg, X, Y)
            r4, t4 = timed(engine.run, sub, pcfg, X, Y, mesh=mesh)
            log(f"  single device {t1:.3f} s, mesh {t4:.3f} s (compile "
                f"included); trace counts {dict(ops.TRACE_COUNTS)}")
            exact = (np.array_equal(r1.cumulative_bytes, r4.cumulative_bytes)
                     and np.array_equal(r1.sync_rounds, r4.sync_rounds))
            bitwise = (np.array_equal(r1.cumulative_loss, r4.cumulative_loss)
                       and np.array_equal(r1.cumulative_errors,
                                          r4.cumulative_errors))
            log(f"  syncs={r1.num_syncs}/{r4.num_syncs} "
                f"bytes={r1.total_bytes}/{r4.total_bytes} "
                f"bytes+sync rounds exact={exact} losses bitwise={bitwise}")
            if not bitwise:
                first = first_true(r1.cumulative_loss != r4.cumulative_loss)
                log(f"  largest difference of a round's summed loss: "
                    f"{max_ulp(r1, r4)} float32 ULP; first differing "
                    f"round {first}")
            if not (exact and bitwise):
                failed.append(f"{name} {kind}")
    check(not failed, "mesh engine matches the single-device engine "
          f"(bytes and sync rounds exact, losses bitwise); failed: {failed}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help="1: phases (a)-(c) on one chip; 4: the "
                         "learner-mesh engine against the single device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package: {e}",
              file=sys.stderr)
        return 2

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}; this smoke "
              "runs only on a TPU", file=sys.stderr)
        return 1
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}; jax {jax.__version__}")
    if args.chips == MESH_CHIPS and len(devs) < MESH_CHIPS:
        print(f"chip_smoke: --chips {MESH_CHIPS} needs {MESH_CHIPS} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")

    dep = deployment(args.seed)
    if args.chips == MESH_CHIPS:
        phases = [("mesh", lambda: phase_mesh(dep))]
    else:
        phases = [("engine SV", lambda: phase_engine_sv(dep)),
                  ("engine RFF", lambda: phase_engine_rff(dep)),
                  ("serving", lambda: phase_serving(dep, args.seed))]
    failed = []
    for name, run in phases:
        _, t = timed(_run_phase, name, run, failed)
        log(f"[{name}] {'FAILED' if name in failed else 'passed'} "
            f"in {t:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _run_phase(name, run, failed) -> None:
    try:
        run()
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        failed.append(name)


if __name__ == "__main__":
    sys.exit(main())
