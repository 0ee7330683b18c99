"""Serial simulation of the m-learner + coordinator system.

This is the paper-faithful experiment driver: m local learners process
individual streams; the chosen protocol (none / continuous / periodic /
dynamic) decides when to synchronize; the ledger accounts bytes exactly
as in Sec. 3.  It produces the quantities plotted in Figs. 1 and 2:
cumulative loss/error, cumulative communication (over time), number of
synchronizations, and quiescence behaviour.

The per-round compute (m learner updates + local-condition checks) is
one jitted function; the byte accounting (set algebra over sv_ids) runs
in numpy outside jit, mirroring a real deployment where the
coordinator's bookkeeping is host-side.  That host round-trip per
round makes this driver the *oracle*, not the fast path: the
device-resident ``lax.scan`` engine (core/engine.py, DESIGN.md Sec. 7)
reproduces this driver's byte ledger exactly while touching the host
once per run, and is what the figure benchmarks use.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import accounting, compression, learners, rkhs
from .learners import LearnerConfig
from .protocol import ProtocolConfig
from .rkhs import KernelSpec, SVModel


@dataclasses.dataclass
class SimResult:
    """Everything the figure benchmarks need."""

    cumulative_loss: np.ndarray        # (T,) summed over learners
    cumulative_bytes: np.ndarray       # (T,)
    cumulative_errors: np.ndarray      # (T,) 0/1 prediction mistakes
    sync_rounds: np.ndarray            # indices where a sync happened
    divergences: np.ndarray            # (T,) measured delta(f_t)
    eps_history: np.ndarray            # compression errors at syncs
    num_syncs: int
    total_bytes: int
    total_loss: float

    @property
    def quiescence_round(self) -> Optional[int]:
        """First round index q from which the run is synchronization-
        free through the end — the boundary convention shared with
        ``criterion.quiescent`` (which is defined in terms of this
        property): ``0`` when the run never synchronized, ``s + 1``
        when the last sync landed at round ``s < T - 1``, and ``None``
        when a sync landed on the final round (quiescence was never
        observed within the run)."""
        if len(self.sync_rounds) == 0:
            return 0
        last = int(self.sync_rounds[-1])
        T = len(self.cumulative_loss)
        return last + 1 if last + 1 <= T - 1 else None

    @classmethod
    def from_round_series(
        cls,
        losses: np.ndarray,       # (T,) per-round summed loss
        errors: np.ndarray,       # (T,) per-round summed errors
        round_bytes: np.ndarray,  # (T,) bytes charged per round
        divergences: np.ndarray,  # (T,) or (0,) measured delta(f_t)
        sync_flags: np.ndarray,   # (T,) bool, True where a sync happened
        eps: np.ndarray,          # (T,) or (0,) compression error per round
    ) -> "SimResult":
        """Build a SimResult from per-round series (the scan engine's
        output format).  Accumulation happens here in float64/int64,
        matching the legacy drivers' host-side accumulators."""
        losses = np.asarray(losses, np.float64)
        errors = np.asarray(errors, np.float64)
        sync_flags = np.asarray(sync_flags, bool)
        cum_bytes = np.cumsum(np.asarray(round_bytes, np.int64))
        cum_loss = np.cumsum(losses)
        return cls(
            cumulative_loss=cum_loss,
            cumulative_bytes=cum_bytes,
            cumulative_errors=np.cumsum(errors),
            sync_rounds=np.nonzero(sync_flags)[0].astype(np.int64),
            divergences=np.asarray(divergences, np.float64),
            eps_history=(np.asarray(eps, np.float64)[sync_flags]
                         if len(eps) else np.zeros((0,))),
            num_syncs=int(sync_flags.sum()),
            total_bytes=int(cum_bytes[-1]) if len(cum_bytes) else 0,
            total_loss=float(cum_loss[-1]) if len(cum_loss) else 0.0,
        )


# ---------------------------------------------------------------------------
# Kernel-learner simulation
# ---------------------------------------------------------------------------


def run_kernel_simulation(
    lcfg: LearnerConfig,
    pcfg: ProtocolConfig,
    X: np.ndarray,          # (T, m, d) per-round per-learner inputs
    Y: np.ndarray,          # (T, m)
    sync_budget: Optional[int] = None,
    compress_method: str = compression.DEFAULT_METHOD,
) -> SimResult:
    """Run T rounds of m kernel learners under the given protocol.

    sync_budget: budget of the synchronized (averaged) model that is
    shipped back to the learners.  Defaults to the learner budget tau —
    i.e. the average (union, budget m*tau) is compressed back to tau
    before redistribution; the measured compression error feeds the
    epsilon term of Thm. 4.
    """
    T, m, d = X.shape
    assert d == lcfg.dim
    learners.check_id_capacity(T)
    tau = lcfg.budget
    sync_budget = sync_budget or tau
    spec = lcfg.kernel

    states = [learners.init_state(lcfg, i) for i in range(m)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    vupdate = jax.jit(jax.vmap(partial(learners.update, lcfg)))

    @jax.jit
    def local_distances(models: SVModel, ref: SVModel):
        return rkhs.stacked_dist_to(spec, models, ref)

    @jax.jit
    def divergence(models: SVModel):
        return rkhs.divergence_stacked(spec, models)

    @jax.jit
    def make_sync(models: SVModel):
        fbar = rkhs.average_stacked(models)          # budget m*tau
        fsync, eps = compression.compress(spec, fbar, sync_budget, compress_method)
        return fsync, eps

    def set_all(models: SVModel, fsync: SVModel) -> SVModel:
        # learners adopt the (compressed) average; pad/truncate to tau.
        one = rkhs.pad_to_budget(fsync, tau)
        return SVModel(
            sv=jnp.broadcast_to(one.sv[None], (m,) + one.sv.shape),
            alpha=jnp.broadcast_to(one.alpha[None], (m,) + one.alpha.shape),
            sv_id=jnp.broadcast_to(one.sv_id[None], (m,) + one.sv_id.shape),
        )

    # reference model starts as the (empty) average
    reference, _ = make_sync(stacked.model)

    ledger = accounting.CommunicationLedger(accounting.ByteModel(dim=d))
    cum_loss, cum_bytes, cum_err, divs, eps_hist = [], [], [], [], []
    total_loss = 0.0
    total_err = 0.0

    vpredict = jax.jit(
        jax.vmap(lambda f, x: rkhs.predict(spec, f, x[None])[0])
    )

    for t in range(T):
        xb = jnp.asarray(X[t]); yb = jnp.asarray(Y[t])
        # service quality before update (prediction errors); the hinge
        # decision rule is deterministic at a zero margin (yhat >= 0
        # predicts +1), identically in every driver — see
        # engine._err_terms
        yhat = vpredict(stacked.model, xb)
        if lcfg.loss == "hinge":
            pred = jnp.where(yhat >= 0, 1.0, -1.0)
            total_err += float(jnp.sum(pred != yb))
        else:
            total_err += float(jnp.sum((yhat - yb) ** 2))

        stacked, losses = vupdate(stacked, (xb, yb))
        total_loss += float(jnp.sum(losses))

        models = stacked.model
        do_sync = False
        if pcfg.kind == "continuous":
            do_sync = True
        elif pcfg.kind == "periodic":
            do_sync = ((t + 1) % pcfg.period) == 0
        elif pcfg.kind == "dynamic":
            if ((t + 1) % pcfg.mini_batch) == 0:
                dists = np.asarray(local_distances(models, reference))
                do_sync = bool((dists > pcfg.delta).any())

        if do_sync:
            ids = np.asarray(models.sv_id)
            fsync, eps = make_sync(models)
            eps_hist.append(float(eps))
            new_models = set_all(models, fsync)
            stacked = stacked._replace(model=new_models)
            reference = jax.tree.map(lambda x: x, fsync)
            ledger.record_kernel_sync([ids[i] for i in range(m)], t)
        else:
            ledger.record_no_sync()

        divs.append(float(divergence(stacked.model)))
        cum_loss.append(total_loss)
        cum_err.append(total_err)
        cum_bytes.append(ledger.total)

    return SimResult(
        cumulative_loss=np.asarray(cum_loss),
        cumulative_bytes=np.asarray(cum_bytes, dtype=np.int64),
        cumulative_errors=np.asarray(cum_err),
        sync_rounds=np.asarray(ledger.sync_rounds, dtype=np.int64),
        divergences=np.asarray(divs),
        eps_history=np.asarray(eps_hist),
        num_syncs=len(ledger.sync_rounds),
        total_bytes=int(ledger.total),
        total_loss=float(total_loss),
    )


# ---------------------------------------------------------------------------
# Linear-learner simulation (the paper's baseline hypothesis class)
# ---------------------------------------------------------------------------


def run_linear_simulation(
    lcfg: LearnerConfig,
    pcfg: ProtocolConfig,
    X: np.ndarray,
    Y: np.ndarray,
) -> SimResult:
    T, m, d = X.shape
    states = [learners.init_state(lcfg, i) for i in range(m)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    vupdate = jax.jit(jax.vmap(partial(learners.update, lcfg)))

    @jax.jit
    def dists_to(st, ref):
        return jax.vmap(
            lambda s: rkhs.rowsum((s.w - ref.w) ** 2) + (s.b - ref.b) ** 2
        )(st)

    @jax.jit
    def diverg(st):
        wbar = jnp.mean(st.w, axis=0); bbar = jnp.mean(st.b)
        return jnp.mean(rkhs.rowsum((st.w - wbar[None, :]) ** 2)
                        + (st.b - bbar) ** 2)

    @jax.jit
    def avg(st):
        return learners.LinearLearnerState(
            w=jnp.mean(st.w, axis=0), b=jnp.mean(st.b)
        )

    reference = avg(stacked)
    ledger = accounting.CommunicationLedger(accounting.ByteModel(dim=d))
    cum_loss, cum_bytes, cum_err, divs = [], [], [], []
    total_loss = 0.0; total_err = 0.0
    nparams = d + 1

    # multiply + fixed-order sum, matching the substrate layer's
    # layout-independent prediction floats (rkhs.rowsum rationale)
    vpredict = jax.jit(jax.vmap(lambda s, x: rkhs.rowsum(s.w * x) + s.b))

    for t in range(T):
        xb = jnp.asarray(X[t]); yb = jnp.asarray(Y[t])
        yhat = vpredict(stacked, xb)
        if lcfg.loss == "hinge":
            pred = jnp.where(yhat >= 0, 1.0, -1.0)   # zero margin -> +1
            total_err += float(jnp.sum(pred != yb))
        else:
            total_err += float(jnp.sum((yhat - yb) ** 2))

        stacked, losses = vupdate(stacked, (xb, yb))
        total_loss += float(jnp.sum(losses))

        do_sync = False
        if pcfg.kind == "continuous":
            do_sync = True
        elif pcfg.kind == "periodic":
            do_sync = ((t + 1) % pcfg.period) == 0
        elif pcfg.kind == "dynamic":
            if ((t + 1) % pcfg.mini_batch) == 0:
                dists = np.asarray(dists_to(stacked, reference))
                do_sync = bool((dists > pcfg.delta).any())

        if do_sync:
            mean = avg(stacked)
            stacked = learners.LinearLearnerState(
                w=jnp.broadcast_to(mean.w[None], stacked.w.shape),
                b=jnp.broadcast_to(mean.b[None], stacked.b.shape),
            )
            reference = mean
            ledger.record_linear_sync(nparams, m, t)
        else:
            ledger.record_no_sync()

        divs.append(float(diverg(stacked)))
        cum_loss.append(total_loss)
        cum_err.append(total_err)
        cum_bytes.append(ledger.total)

    return SimResult(
        cumulative_loss=np.asarray(cum_loss),
        cumulative_bytes=np.asarray(cum_bytes, dtype=np.int64),
        cumulative_errors=np.asarray(cum_err),
        sync_rounds=np.asarray(ledger.sync_rounds, dtype=np.int64),
        divergences=np.asarray(divs),
        eps_history=np.zeros((0,)),
        num_syncs=len(ledger.sync_rounds),
        total_bytes=int(ledger.total),
        total_loss=float(total_loss),
    )
