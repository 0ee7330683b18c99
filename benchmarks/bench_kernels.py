"""Microbenchmarks of the Pallas compute kernels vs their jnp oracles
(CPU interpret mode here; the derived columns report the TPU-relevant
HBM-traffic savings of the fused paths).

Two gated claims ride in this suite (checked by tools/bench_compare.py
against benchmarks/baselines/BENCH_kernels.json in CI):

- ``kernels/fused_round_sv/fused_step_faster`` — the fused scan round
  (one shared predict feeding ``kernel_update_from_yhat``) beats the
  legacy composed predict+update on the SAME backend.  Measured on the
  reference (jnp) path so the number is a real CPU latency, not an
  interpret-mode artifact; the structural saving (half the Gram work
  per round) is backend-independent.
- ``kernels/serve_bucket/bucket_predict_hits_pallas`` — replaying a
  query-bearing stream through the serving engine with an ENGAGED
  pallas SV substrate routes bucketized predicts through the fused
  ``ops.sv_predict`` kernel, observed via ``ops.TRACE_COUNTS``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as core_engine
from repro.core.learners import LearnerConfig
from repro.core.protocol import ProtocolConfig
from repro.core.rkhs import KernelSpec
from repro.core.substrate import SVSubstrate
from repro.kernels import ops, ref
from repro.serving.engine import serve_stream

from .common import Row, timeit


def _sv_sub(budget: int, d: int, backend: str) -> SVSubstrate:
    return SVSubstrate(
        lcfg=LearnerConfig(algo="kernel_sgd", loss="hinge", eta=0.5,
                           lam=0.01, budget=budget, dim=d,
                           kernel=KernelSpec("gaussian", gamma=0.3)),
        backend=backend)


def _fused_round_rows(quick: bool):
    """fused round_stacked vs composed predict+update, reference path."""
    # same shape in quick mode: the claim needs the Gram-dominated
    # regime, where the structural 2-grams -> 1-gram saving shows up
    # above timer noise
    m, budget, d = (8, 1024, 64)
    sub = _sv_sub(budget, d, "reference")
    rng = np.random.default_rng(1)
    state = sub.init(m)
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=(m,)), jnp.float32)
    # warm the buffers so both timings see full SV sets
    warm = jax.jit(lambda st, x, y: sub.round_stacked(st, (x, y))[0])
    for t in range(budget // m + 2):
        state = warm(state, x + 0.01 * t, y)

    # composed = the pre-refactor shape of a round: predict and update
    # as SEPARATE jitted dispatches (XLA cannot share the Gram across
    # them, and each pays its own dispatch).  Fused = one
    # round_stacked call where update consumes predict's value.
    predict_j = jax.jit(lambda st, x: sub.predict(sub.models_of(st), x))
    update_j = jax.jit(lambda st, x, y: sub.update(st, (x, y)))

    def composed(st, x, y):
        return predict_j(st, x), update_j(st, x, y)

    fused = jax.jit(lambda st, x, y: sub.round_stacked(st, (x, y)))
    # min-of-3 means: scheduler spikes on shared CI runners must not
    # flip the gated claim
    us_composed = min(timeit(composed, state, x, y) for _ in range(3))
    us_fused = min(timeit(fused, state, x, y) for _ in range(3))
    faster = bool(us_fused < us_composed)
    return [
        Row("kernels/composed_round_sv", us_composed,
            f"m={m};budget={budget};d={d};grams_per_round=2"),
        Row("kernels/fused_round_sv", us_fused,
            f"grams_per_round=1;speedup={us_composed / us_fused:.2f}x;"
            f"fused_step_faster={faster}"),
    ]


def _serve_bucket_rows(quick: bool):
    """engaged pallas SV serving: the bucket predict is ONE fused
    sv_predict launch, proven by the launch counter."""
    T, m, d = (30, 3, 8) if quick else (60, 3, 8)
    budget = 130                                  # >= _MIN_PALLAS: engaged
    rng = np.random.default_rng(2)
    X = np.asarray(rng.normal(size=(T, m, d)), np.float32)
    Y = np.asarray(rng.choice([-1.0, 1.0], size=(T, m)), np.float32)
    sub = _sv_sub(budget, d, "pallas")
    pcfg = ProtocolConfig(kind="periodic", period=10)
    before = ops.TRACE_COUNTS["sv_predict"]
    t0 = time.perf_counter()
    res = serve_stream(sub, pcfg, X, Y, queries_per_round=1.0)
    wall_us = (time.perf_counter() - t0) * 1e6
    hits = ops.TRACE_COUNTS["sv_predict"] - before
    # ledger parity with the scan engine is part of the claim: routing
    # predicts through the fused kernel must not perturb the protocol
    ref_res = core_engine.run(sub, pcfg, X, Y)
    ok = bool(hits > 0
              and res.num_syncs == ref_res.num_syncs
              and res.total_bytes == ref_res.total_bytes)
    return [Row("kernels/serve_bucket", wall_us,
                f"budget={budget};queries={res.num_requests};"
                f"sv_predict_launches={hits};"
                f"bucket_predict_hits_pallas={ok}")]


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    M = 256 if quick else 512
    d = 64
    X = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(M,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(M,)), jnp.float32)

    spec = KernelSpec("gaussian", gamma=0.5)
    rows = []
    g_ref = jax.jit(lambda X, Y: ref.gram_ref(X, Y, gamma=0.5))
    us = timeit(g_ref, X, Y)
    rows.append(Row("kernels/gram_jnp_oracle", us, f"M={M};d={d}"))
    us = timeit(lambda: ops.gram(spec, X, Y))
    rows.append(Row("kernels/gram_pallas_interpret", us,
                    "validated=allclose;mode=interpret(CPU)"))

    q_ref = jax.jit(lambda X, Y, a, b: ref.quadform_ref(X, Y, a, b, gamma=0.5))
    us = timeit(q_ref, X, Y, a, b)
    hbm_naive = M * M * 4
    hbm_fused = 2 * M * d * 4
    rows.append(Row("kernels/quadform_jnp_oracle", us,
                    f"hbm_gram_bytes={hbm_naive}"))
    us = timeit(lambda: ops.quadform(spec, X, Y, a, b))
    rows.append(Row("kernels/quadform_pallas_interpret", us,
                    f"hbm_stream_bytes={hbm_fused};"
                    f"traffic_saving={hbm_naive / hbm_fused:.0f}x"))

    W = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    bias = jnp.asarray(rng.uniform(size=(M,)) * 6.28, jnp.float32)
    r_ref = jax.jit(lambda X: ref.rff_ref(X, W, bias))
    us = timeit(r_ref, X)
    rows.append(Row("kernels/rff_jnp_oracle", us, f"D={M}"))
    us = timeit(lambda: ops.rff_features(X, W, bias))
    rows.append(Row("kernels/rff_pallas_interpret", us,
                    "fused=proj+bias+cos"))

    # fused sv_predict: one launch covers a (B, N, d) stacked predict
    B, N = (4, 192) if quick else (8, 384)
    Xs = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    SVs = jnp.asarray(rng.normal(size=(B, N, d)), jnp.float32)
    As = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    sv_ref = jax.jit(lambda X, S, A: ref.sv_predict_ref(X, S, A, gamma=0.5))
    us = timeit(sv_ref, Xs, SVs, As)
    rows.append(Row("kernels/sv_predict_jnp_oracle", us, f"B={B};N={N}"))
    us = timeit(lambda: ops.sv_predict(spec, Xs, SVs, As))
    rows.append(Row("kernels/sv_predict_pallas_interpret", us,
                    "fused=gram+mask+reduce;row_bits=batch_invariant"))

    # fused primal step: featurize + predict + loss/grad + update in one
    D = 128 if quick else 256
    Xp = jnp.asarray(rng.normal(size=(M, d)), jnp.float32)
    Yp = jnp.asarray(rng.choice([-1.0, 1.0], size=(M,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(M,)), jnp.float32)
    Wp = jnp.asarray(rng.normal(size=(D, d)), jnp.float32)
    bp = jnp.asarray(rng.uniform(size=(D,)) * 6.28, jnp.float32)
    scale = float(np.sqrt(2.0 / D))
    p_ref = jax.jit(lambda *t: ref.primal_step_ref(
        *t, W=Wp, bias=bp, scale=scale, loss="hinge", eta=0.5, lam=0.01))
    us = timeit(p_ref, Xp, Yp, w, bb)
    rows.append(Row("kernels/rff_step_jnp_oracle", us, f"B={M};D={D}"))
    us = timeit(lambda: ops.fused_primal_step(
        Xp, Yp, w, bb, W=Wp, bias=bp, scale=scale, loss="hinge",
        eta=0.5, lam=0.01))
    rows.append(Row("kernels/rff_step_pallas_interpret", us,
                    "fused=featurize+dot+lossgrad+update"))

    rows.extend(_fused_round_rows(quick))
    rows.extend(_serve_bucket_rows(quick))
    return rows


if __name__ == "__main__":
    from .common import print_rows
    print_rows(run())
