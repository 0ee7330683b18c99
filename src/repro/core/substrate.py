"""Learner substrates: one protocol-facing model interface (DESIGN.md Sec. 8).

The paper's protocols are agnostic to how a learner represents its
model: they only ever (1) run the local update, (2) average the m
models (Prop. 2), (3) measure distance to the reference model for the
local conditions, and (4) pay Sec. 3 bytes when a synchronization ships
models around.  A :class:`Substrate` packages exactly those operations,
so the scan engine (core/engine.py) and the asynchronous runtime
(repro/runtime/) each have ONE code path serving every representation:

- :class:`SVSubstrate`      — dual support-vector expansion in the RKHS
  (``rkhs.SVModel``); sync payloads use the delta-encoded id accounting
  (``accounting.DeviceLedger`` under jit, id sets on the host).
- :class:`RFFSubstrate`     — primal weights over D random Fourier
  features (paper Sec. 4 "future work", cf. Bouboulis et al.): kernel-
  quality models at *linear-model* communication cost — every sync
  costs O(m D) bytes independent of the rounds seen, so Cor. 8's strict
  adaptivity applies verbatim.
- :class:`LinearSubstrate`  — the paper's Euclidean baselines.

Substrates are frozen (hashable) dataclasses: the engine's compiled-
function cache and the runtime's jitted node-op cache key on them
directly.

Backend dispatch: ``backend="reference"`` evaluates kernel algebra with
the pure-jnp definitions in core/rkhs.py and core/rff.py (the semantic
oracles); ``backend="pallas"`` routes ``predict`` / ``predict_batch`` /
``dist_to_ref`` / ``divergence`` and the fused scan round through the
fused TPU kernels ``kernels.ops.sv_predict`` / ``fused_primal_step`` /
``quadform`` / ``rff_features`` (interpret mode validates them on
CPU).  The dispatch is *engage-aware* (``kernels.ops.engages``): below
the Pallas launch threshold the pallas backend runs the exact
reference expressions, so small-model pallas runs are bit-identical to
``backend="reference"`` — which is what makes the Def. 1 byte ledger
backend-independent by construction (tools/substrate_matrix.py pins
it across the full substrate x protocol x driver matrix).

Two faces, one contract
-----------------------
Scan face (jit-side, stacked over the learner axis m):
``init / predict / update / average_stacked / adopt / dist_to_ref /
divergence / ledger_init / sync_payload``.  ``sync_payload`` implements
the Sec. 3 byte accounting *for this representation*: delta-encoded
support-vector sets for SV, a fixed ``2 m (D+1) B`` for RFF, a fixed
``2 m (d+1) B`` for linear.

Node face (host-side, one model per node, used by repro/runtime):
``init_node / node_model / update_one / predict_one / dist_one /
init_reference / upload_payload / download_payload_bytes / aggregate /
adopt_node`` plus the snapshot hooks the async harness uses to record
round-indexed divergences.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import accounting, compression, learners, rff, rkhs
from .learners import LearnerConfig, LinearLearnerState
from .rff import RFFLearnerState, RFFSpec
from .rkhs import SVModel

Array = jnp.ndarray

_BACKENDS = ("reference", "pallas")


def _kops():
    """Lazy import of the Pallas op wrappers (kernels.ops)."""
    from ..kernels import ops
    return ops


# ---------------------------------------------------------------------------
# Interface
# ---------------------------------------------------------------------------


class Substrate:
    """Protocol-facing model representation (see module docstring).

    Class attributes every implementation sets:

    - ``loss``: the surrogate loss name ("hinge" | "squared") — the
      engine uses it to measure service errors.
    - ``input_dim``: expected feature dimension d of the stream.
    - ``has_eps``: syncs produce a compression-error series epsilon
      (Thm. 4's epsilon term); False for exact (primal) substrates.
    - ``free_divergence``: recording delta(f_t) is O(m d)-cheap, so the
      engine records it every round (matching the legacy linear
      driver); False makes recording opt-in (SV: a full union Gram).
    - ``guarded_dist_check``: wrap the dynamic local-condition check in
      ``lax.cond`` so the (expensive) distance computation only runs on
      check rounds; False evaluates it unconditionally (cheap).
    """

    loss: str = "hinge"
    has_eps: bool = False
    free_divergence: bool = True
    guarded_dist_check: bool = False

    # -- scan face ----------------------------------------------------------

    def init(self, m: int):
        raise NotImplementedError

    def models_of(self, state):
        return state

    def with_models(self, state, models):
        return models

    def predict(self, models, x: Array) -> Array:
        raise NotImplementedError

    def predict_batch(self, models, lids: Array, Xb: Array) -> Array:
        """Serve a padded batch of predict requests from the stacked
        models: request ``i`` is answered by learner ``lids[i]``'s
        current model on input ``Xb[i]`` -> (n,) predictions.

        This is the serving engine's hot path (DESIGN.md Sec. 10):
        ``lids`` (n,) int32 home-learner ids, ``Xb`` (n, d) inputs, n a
        *static bucket size* so each bucket keys one compile-cache
        entry.  Padding rows repeat a learner id already present in
        the batch (the serving engine uses the chunk's first, keeping
        the gather shard-local under mesh routing) with zero inputs,
        and are discarded by the caller.

        Bit-exactness contract: row ``i``'s floats equal
        ``predict_one(models[lids[i]], Xb[i])`` regardless of how many
        rows share the call — guaranteed because every loss-feeding
        contraction in this repo is an explicit multiply + last-axis
        reduce (DESIGN.md Sec. 9), so a row's accumulation order never
        depends on the batch around it (tests/test_serving.py pins it).
        """
        picked = jax.tree.map(lambda v: v[lids], models)
        return jax.vmap(self.predict_one)(picked, Xb)

    def update(self, state, example):
        raise NotImplementedError

    # A substrate whose stacked predict and update share expensive work
    # (the RFF feature map, the SV Gram rows) can set fused_scan_round
    # and override round_stacked as ONE fused computation; the scan
    # engine (core/engine.py) then replaces its separate predict +
    # update calls with it.  The default composition is the engine's
    # legacy order, so overriding is purely an optimization — the
    # returned floats must not change (tests/test_backend_parity.py).
    fused_scan_round: bool = False

    def round_stacked(self, state, example):
        """One stacked round -> (new_state, losses, yhat_pre_update)."""
        yhat = self.predict(self.models_of(state), example[0])
        new_state, losses = self.update(state, example)
        return new_state, losses, yhat

    def average_stacked(self, models):
        """(f_sync, eps): the Prop. 2 average prepared for
        redistribution — compressed to the sync budget for SV, exact
        (eps = 0) for primal substrates."""
        raise NotImplementedError

    # -- participation face (DESIGN.md Sec. 15) -----------------------------
    #
    # The population layer synchronizes a sampled cohort: the Prop. 2
    # average, the Sec. 3 payload, and the ring pricing all restrict to
    # the participating learners, and a learner rejoining after churn
    # re-adopts the reference at a Sec. 3 download price.  Contract:
    # with ``mask`` all-True every masked op returns the SAME floats /
    # integers as its unmasked twin (tests/test_population.py pins it
    # bitwise) — that degenerate case is what makes the population
    # engine path provable against ``engine.run``.

    def average_stacked_masked(self, models, mask):
        """(f_sync, eps) over the participating cohort only: the
        Prop. 2 average of the masked learners.  ``mask`` (m,) bool;
        an empty cohort must not divide by zero (the engine never
        syncs one, but ``lax.cond`` lowers to a select under some
        transforms, so the untaken branch still executes)."""
        raise NotImplementedError

    def sync_payload_masked(self, models, mask, ledger):
        """Sec. 3 bytes of one cohort synchronization
        -> (int32 bytes, ledger): non-participants neither upload nor
        download and are excluded from the shipped union."""
        raise NotImplementedError

    def rejoin_payload_bytes(self, models, ref, rejoin):
        """int32 Sec. 3 download bytes of re-``adopt``-ing the
        reference on the ``rejoin`` (m,) bool learners — the recovery
        half of churn (DESIGN.md Sec. 15)."""
        raise NotImplementedError

    def allreduce_sync_bytes_masked(self, count):
        """Traced-int32 ring bytes of one cohort synchronization under
        ``topology="allreduce"`` — ``allreduce_sync_bytes`` with the
        static m replaced by the traced cohort size ``count``."""
        raise NotImplementedError

    def adopt(self, models, fsync):
        raise NotImplementedError

    def dist_to_ref(self, models, ref) -> Array:
        raise NotImplementedError

    def dist_to_ref_each(self, models, ref_stacked) -> Array:
        """Per-learner distance to a PER-LEARNER reference slice.

        The mesh-sharded engine (DESIGN.md Sec. 9) keeps the Sec. 3
        stacked reference sliced next to each learner, so the dynamic
        local condition is a purely device-local reduction:
        ``ref_stacked`` carries the same leading learner axis as
        ``models`` (every slice holds the same synchronized model).
        """
        return jax.vmap(self.dist_one)(models, ref_stacked)

    def divergence(self, models) -> Array:
        raise NotImplementedError

    def ledger_init(self, m: int):
        return ()

    def sync_payload(self, models, ledger):
        """Sec. 3 bytes of one synchronization -> (int32 bytes, ledger)."""
        raise NotImplementedError

    def allreduce_sync_bytes(self, m: int) -> int:
        """TOTAL ring bytes of one mesh synchronization
        (``topology="allreduce"``, DESIGN.md Sec. 9): the cost of the
        collective that replaces the coordinator's up/downloads when
        the learner axis is sharded.  A host-side constant — unlike
        ``sync_payload`` it never depends on the rounds seen."""
        raise NotImplementedError

    def validate(self, T: int, m: int, d: int) -> None:
        if d != self.input_dim:
            raise ValueError(
                f"stream dim {d} != substrate dim {self.input_dim}")

    # -- node face ----------------------------------------------------------

    def init_node(self, idx: int):
        raise NotImplementedError

    def node_model(self, state):
        return state

    def update_one(self, state, example):
        raise NotImplementedError

    def predict_one(self, model, x: Array) -> Array:
        raise NotImplementedError

    def dist_one(self, model, ref) -> Array:
        raise NotImplementedError

    # A substrate whose predict and update share expensive work (e.g.
    # the RFF feature map) can set fused_node_round and implement
    # round_one(state, example) -> (new_state, loss, yhat_pre_update)
    # as ONE jitted computation; otherwise the runtime composes the
    # separately-jitted predict_one / update_one, which keeps node
    # numerics identical to the legacy per-op dispatch.
    fused_node_round: bool = False

    def round_one(self, state, example):
        raise NotImplementedError

    def init_reference(self):
        raise NotImplementedError

    def upload_payload(self, bm: accounting.ByteModel, state,
                       known: Set[int]):
        """(model, ids, nbytes) for a learner->coordinator upload."""
        raise NotImplementedError

    def download_payload_bytes(self, bm: accounting.ByteModel,
                               union: Set[int], receiver_ids: Set[int]) -> int:
        raise NotImplementedError

    def aggregate(self, reference, models: Sequence, weights: Sequence[float]):
        """Staleness-weighted aggregation -> (fsync, eps | None, union)."""
        raise NotImplementedError

    def adopt_node(self, state, fsync):
        raise NotImplementedError

    # -- async-harness snapshot hooks ---------------------------------------

    def snapshot_buffers(self, T: int, m: int) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def write_snapshot(self, bufs, t: int, i: int, model) -> None:
        raise NotImplementedError

    def divergence_series(self, bufs) -> np.ndarray:
        raise NotImplementedError


class NodeOps(NamedTuple):
    """Jitted per-node compute, shared across nodes (one compile).

    ``round`` performs one full learner round: it returns
    (new_state, loss, yhat) with yhat the pre-update prediction the
    harness measures service errors with.
    """

    update: Any
    predict: Any
    dist: Any
    round: Any


@functools.lru_cache(maxsize=None)
def node_ops(sub: Substrate) -> NodeOps:
    update = jax.jit(sub.update_one)
    predict = jax.jit(sub.predict_one)
    if sub.fused_node_round:
        rnd = jax.jit(sub.round_one)
    else:
        def rnd(state, example):
            yhat = predict(sub.node_model(state), example[0])
            new_state, loss = update(state, example)
            return new_state, loss, yhat
    return NodeOps(
        update=update,
        predict=predict,
        dist=jax.jit(sub.dist_one),
        round=rnd,
    )


# ---------------------------------------------------------------------------
# SV substrate (dual RKHS expansion)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SVSubstrate(Substrate):
    """Budgeted support-vector expansion + DeviceLedger delta accounting."""

    lcfg: LearnerConfig = dataclasses.field(default_factory=LearnerConfig)
    sync_budget: int = 0          # 0 -> lcfg.budget
    compress_method: str = compression.DEFAULT_METHOD
    backend: str = "reference"

    has_eps = True
    free_divergence = False
    guarded_dist_check = True

    def __post_init__(self):
        if not self.lcfg.is_kernel:
            raise ValueError("SVSubstrate needs a kernel LearnerConfig")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.sync_budget == 0:
            object.__setattr__(self, "sync_budget", int(self.lcfg.budget))

    @property
    def loss(self) -> str:
        return self.lcfg.loss

    @property
    def input_dim(self) -> int:
        return self.lcfg.dim

    def validate(self, T: int, m: int, d: int) -> None:
        super().validate(T, m, d)
        learners.check_id_capacity(T)

    # -- scan face ----------------------------------------------------------

    def init(self, m: int):
        states = [learners.init_state(self.lcfg, i) for i in range(m)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def models_of(self, state):
        return state.model

    def with_models(self, state, models):
        return state._replace(model=models)

    def _engaged(self) -> bool:
        """Pallas backend AND the SV budget reaches the launch
        threshold.  Below it the reference expressions run verbatim —
        bit-identical to backend="reference" (module docstring)."""
        return self.backend == "pallas" and _kops().engages(self.lcfg.budget)

    def predict(self, models: SVModel, x: Array) -> Array:
        if self._engaged():
            a = jnp.where(rkhs.active_mask(models), models.alpha, 0.0)
            return _kops().sv_predict_spec(self.lcfg.kernel, x, models.sv, a)
        return jax.vmap(lambda f, xi: self.predict_one(f, xi))(models, x)

    def predict_batch(self, models: SVModel, lids: Array, Xb: Array) -> Array:
        # the serving bucket path: one fused sv_predict launch answers
        # the whole bucket.  Row floats still match predict_one —
        # ops.sv_predict's blocks and engagement never depend on the
        # batch size (kernels/ops.py), and each row is its own grid
        # cell — so the serving bit-exactness contract holds on the
        # fused path too (tests/test_backend_parity.py pins it).
        if self._engaged():
            picked = jax.tree.map(lambda v: v[lids], models)
            a = jnp.where(rkhs.active_mask(picked), picked.alpha, 0.0)
            return _kops().sv_predict_spec(self.lcfg.kernel, Xb,
                                           picked.sv, a)
        return super().predict_batch(models, lids, Xb)

    def update(self, state, example):
        return jax.vmap(functools.partial(learners.update, self.lcfg))(
            state, example)

    # one shared predict feeds both the service-error record and the
    # learner update — half the per-round Gram work of the composed
    # path, same floats (kernel_update_from_yhat is kernel_update with
    # the prediction supplied)
    fused_scan_round = True

    def round_stacked(self, state, example):
        x, y = example
        yhat = self.predict(state.model, x)
        upd = functools.partial(learners.kernel_update_from_yhat, self.lcfg)
        new_state, losses = jax.vmap(
            lambda st, xi, yi, yh: upd(st, (xi, yi), yh))(state, x, y, yhat)
        return new_state, losses, yhat

    def average_stacked(self, models: SVModel):
        fbar = rkhs.average_stacked(models)           # budget m*tau
        return compression.compress(self.lcfg.kernel, fbar,
                                    self.sync_budget, self.compress_method)

    def average_stacked_masked(self, models: SVModel, mask):
        # the Prop. 2 average over the cohort: non-participants' slots
        # enter with alpha = 0 / id = -1 (inactive), and the divisor is
        # the cohort size.  With mask all-True this is exactly
        # rkhs.average_stacked — same slot multiset, same order, same
        # float32 division by m — so the compressed result is bitwise
        # identical to average_stacked's (tests/test_population.py).
        m, tau, d = models.sv.shape
        cnt = jnp.sum(mask.astype(jnp.int32))
        # XLA lowers division by the COMPILE-TIME constant m differently
        # from division by a traced scalar (strength reduction), so the
        # full-cohort branch must literally be ``alpha / m`` for the
        # bitwise contract to hold; the where picks it when cnt == m.
        cnt_f = jnp.maximum(cnt, 1).astype(jnp.float32)
        scaled = jnp.where(cnt == m, models.alpha / m, models.alpha / cnt_f)
        alpha = jnp.where(mask[:, None] & (models.sv_id >= 0), scaled, 0.0)
        sv_id = jnp.where(mask[:, None], models.sv_id, -1)
        fbar = SVModel(sv=models.sv.reshape(m * tau, d),
                       alpha=alpha.reshape(m * tau),
                       sv_id=sv_id.reshape(m * tau))
        return compression.compress(self.lcfg.kernel, fbar,
                                    self.sync_budget, self.compress_method)

    def sync_payload_masked(self, models: SVModel, mask, ledger):
        bm = accounting.ByteModel(dim=self.lcfg.dim)
        return accounting.device_sync_bytes_kernel(
            bm, models.sv_id, ledger, mask=mask)

    def rejoin_payload_bytes(self, models: SVModel, ref: SVModel, rejoin):
        bm = accounting.ByteModel(dim=self.lcfg.dim)
        return accounting.device_rejoin_bytes_kernel(
            bm, ref.sv_id, models.sv_id, rejoin)

    def allreduce_sync_bytes_masked(self, count):
        bm = accounting.ByteModel(dim=self.lcfg.dim)
        slot = bm.B_x + bm.dtype_bytes
        # allgather_bytes with traced cohort size: c (c-1) shard_bytes
        # reprolint: allow[ACC01] int32 mirrors allgather_bytes; engine guards the worst case at full m
        return (count * jnp.maximum(count - 1, 0)
                # reprolint: allow[ACC01] int32 mirrors allgather_bytes; engine guards the worst case at full m
                * jnp.asarray(self.lcfg.budget * slot, jnp.int32))

    def adopt(self, models: SVModel, fsync: SVModel) -> SVModel:
        one = rkhs.pad_to_budget(fsync, self.lcfg.budget)
        return SVModel(
            sv=jnp.broadcast_to(one.sv[None], models.sv.shape),
            alpha=jnp.broadcast_to(one.alpha[None], models.alpha.shape),
            sv_id=jnp.broadcast_to(one.sv_id[None], models.sv_id.shape),
        )

    def dist_to_ref(self, models: SVModel, ref: SVModel) -> Array:
        # engage-gated like every pallas branch: the dynamic protocol's
        # sync decisions feed the byte ledger, so the non-engaged
        # pallas path must be the reference expression verbatim
        if self.backend == "pallas" and _kops().engages(
                self.lcfg.budget, self.sync_budget):
            return jax.vmap(lambda f: self.dist_one(f, ref))(models)
        return rkhs.stacked_dist_to(self.lcfg.kernel, models, ref)

    def divergence(self, models: SVModel) -> Array:
        if self._engaged():
            fbar = rkhs.average_stacked(models)
            return jnp.mean(self.dist_to_ref(models, fbar))
        return rkhs.divergence_stacked(self.lcfg.kernel, models)

    def ledger_init(self, m: int):
        return accounting.device_ledger_init(m * self.lcfg.budget)

    def sync_payload(self, models: SVModel, ledger):
        bm = accounting.ByteModel(dim=self.lcfg.dim)
        return accounting.device_sync_bytes_kernel(bm, models.sv_id, ledger)

    def allreduce_sync_bytes(self, m: int) -> int:
        # SV expansions have no slot alignment across learners, so the
        # mesh sync is a ring all-gather of the m budget-tau stacks;
        # each slot ships its vector + id (B_x) and its coefficient.
        bm = accounting.ByteModel(dim=self.lcfg.dim)
        slot = bm.B_x + bm.dtype_bytes
        return accounting.allgather_bytes(self.lcfg.budget * slot, m)

    # -- node face ----------------------------------------------------------

    def init_node(self, idx: int):
        return learners.init_state(self.lcfg, idx)

    def node_model(self, state):
        return state.model

    def update_one(self, state, example):
        return learners.update(self.lcfg, state, example)

    def predict_one(self, model: SVModel, x: Array) -> Array:
        spec = self.lcfg.kernel
        if self._engaged():
            a = jnp.where(rkhs.active_mask(model), model.alpha, 0.0)
            return _kops().sv_predict_spec(
                spec, x[None], model.sv[None], a[None])[0]
        return rkhs.predict(spec, model, x[None])[0]

    def dist_one(self, model: SVModel, ref: SVModel) -> Array:
        spec = self.lcfg.kernel
        if self.backend == "pallas" and _kops().engages(
                model.sv.shape[0], ref.sv.shape[0]):
            af = jnp.where(rkhs.active_mask(model), model.alpha, 0.0)
            ag = jnp.where(rkhs.active_mask(ref), ref.alpha, 0.0)
            return _kops().rkhs_dist_sq_spec(spec, model.sv, ref.sv, af, ag)
        return rkhs.dist_sq(spec, model, ref)

    def init_reference(self):
        ref, _ = compression.compress(
            self.lcfg.kernel, rkhs.empty_model(self.lcfg.budget, self.lcfg.dim),
            self.sync_budget, self.compress_method)
        return ref

    def upload_payload(self, bm, state, known):
        ids = accounting.idset(np.asarray(state.model.sv_id))
        return (state.model, ids,
                accounting.kernel_payload_bytes(bm, ids, known))

    def download_payload_bytes(self, bm, union, receiver_ids):
        return accounting.kernel_payload_bytes(bm, union, receiver_ids)

    def aggregate(self, reference, models, weights):
        """Staleness-weighted RKHS aggregation (FedAsync-style).

        candidate_k = (1 - w_k) r + w_k f_k; the new reference is the
        mean of the candidates compressed to the sync budget.  In an
        RKHS the convex combination is the concatenation of the
        coefficient-scaled expansions; exact-zero coefficients are
        pruned so the degenerate alpha = 1 case produces the identical
        slot multiset as the serial ``rkhs.average_stacked`` — which is
        why the zero-latency async run reproduces the serial ledger
        byte-for-byte (tests/test_runtime.py).
        """
        n = len(models)
        assert n == len(weights) and n > 0
        parts: List[Tuple[SVModel, float]] = []
        for f, w in zip(models, weights):
            parts.append((reference, (1.0 - w)))
            parts.append((f, w))
        mix = _concat_sv(parts)
        # mean over candidates: divide (not multiply by reciprocal) so
        # the n == m full-weight case reproduces average_stacked's floats.
        mix = mix._replace(alpha=mix.alpha / n)
        union = set(int(i) for i in np.asarray(mix.sv_id) if i >= 0)
        fsync, eps = compression.compress(
            self.lcfg.kernel, mix, self.sync_budget, self.compress_method)
        return fsync, float(eps), union

    def adopt_node(self, state, fsync: SVModel):
        return state._replace(model=rkhs.pad_to_budget(fsync, self.lcfg.budget))

    # -- snapshots ----------------------------------------------------------

    def snapshot_buffers(self, T, m):
        tau, d = self.lcfg.budget, self.lcfg.dim
        return {"sv": np.zeros((T, m, tau, d), np.float32),
                "alpha": np.zeros((T, m, tau), np.float32),
                "sv_id": -np.ones((T, m, tau), np.int32)}

    def write_snapshot(self, bufs, t, i, model: SVModel):
        bufs["sv"][t, i] = np.asarray(model.sv)
        bufs["alpha"][t, i] = np.asarray(model.alpha)
        bufs["sv_id"][t, i] = np.asarray(model.sv_id)

    def divergence_series(self, bufs):
        div_t = jax.jit(lambda f: self.divergence(f))
        out = [float(div_t(SVModel(sv=jnp.asarray(bufs["sv"][t]),
                                   alpha=jnp.asarray(bufs["alpha"][t]),
                                   sv_id=jnp.asarray(bufs["sv_id"][t]))))
               for t in range(bufs["sv"].shape[0])]
        return np.asarray(out)


def _concat_sv(parts: Sequence[Tuple[SVModel, float]]) -> SVModel:
    """Concatenate coefficient-scaled expansions; prune exact zeros.

    Pruning (alpha == 0 -> slot inactive) keeps the degenerate
    full-weight case bit-identical to ``rkhs.average_stacked``: the
    reference's slots enter with weight exactly 0 and vanish, leaving
    the same active-slot multiset in the same order.
    """
    svs, alphas, ids = [], [], []
    for model, w in parts:
        svs.append(np.asarray(model.sv))
        alphas.append(np.asarray(model.alpha) * np.float32(w))
        ids.append(np.asarray(model.sv_id))
    sv = np.concatenate(svs, axis=0)
    alpha = np.concatenate(alphas, axis=0).astype(np.float32)
    sv_id = np.concatenate(ids, axis=0)
    dead = (alpha == 0.0) | (sv_id < 0)
    sv_id = np.where(dead, -1, sv_id)
    sv = np.where(dead[:, None], 0.0, sv).astype(np.float32)
    alpha = np.where(dead, 0.0, alpha)
    return SVModel(sv=jnp.asarray(sv), alpha=jnp.asarray(alpha),
                   sv_id=jnp.asarray(sv_id, jnp.int32))


# ---------------------------------------------------------------------------
# Primal substrates share the (w, b) aggregation and snapshot logic
# ---------------------------------------------------------------------------


class _PrimalSubstrate(Substrate):
    """Shared logic for fixed-size (w, b) models (linear and RFF).

    The representation is a weight vector: Prop. 2 averaging is the
    plain mean, distance is Euclidean, and a synchronization costs a
    fixed ``2 m (num_params) B`` bytes — independent of rounds seen, so
    Cor. 8's strictly-adaptive communication bound applies verbatim
    (the RFF case is exactly the paper's Sec. 4 proposal).
    """

    has_eps = False
    free_divergence = True
    guarded_dist_check = False

    # num_params of one model (w and b), for the Sec. 3 linear accounting
    @property
    def num_params(self) -> int:
        raise NotImplementedError

    def _state_cls(self):
        raise NotImplementedError

    def average_stacked(self, models):
        cls = self._state_cls()
        mean = cls(w=jnp.mean(models.w, axis=0), b=jnp.mean(models.b))
        return mean, jnp.zeros((), jnp.float32)

    def adopt(self, models, fsync):
        cls = self._state_cls()
        return cls(w=jnp.broadcast_to(fsync.w[None], models.w.shape),
                   b=jnp.broadcast_to(fsync.b[None], models.b.shape))

    def dist_to_ref(self, models, ref) -> Array:
        return jax.vmap(
            lambda s: rkhs.rowsum((s.w - ref.w) ** 2) + (s.b - ref.b) ** 2
        )(models)

    def divergence(self, models) -> Array:
        wbar = jnp.mean(models.w, axis=0)
        bbar = jnp.mean(models.b)
        return jnp.mean(rkhs.rowsum((models.w - wbar[None, :]) ** 2)
                        + (models.b - bbar) ** 2)

    def sync_payload(self, models, ledger):
        m = models.w.shape[0]
        nbytes = accounting.sync_bytes_linear(self.num_params, m)
        return jnp.asarray(nbytes, jnp.int32), ledger

    def allreduce_sync_bytes(self, m: int) -> int:
        # fixed-size primal vectors reduce-scatter + all-gather
        return accounting.allreduce_bytes(self.num_params, m)

    # -- participation face (DESIGN.md Sec. 15) -----------------------------

    def average_stacked_masked(self, models, mask):
        # masked Prop. 2 mean: sum the cohort's weights in stacked
        # order, divide by the cohort size.  With mask all-True this is
        # sum/m in the same reduction order as jnp.mean — bitwise
        # identical to average_stacked (tests/test_population.py).
        cls = self._state_cls()
        m = models.w.shape[0]
        cnt = jnp.sum(mask.astype(jnp.int32))
        # division by the compile-time constant m is strength-reduced
        # by XLA; division by a traced scalar is not — the full-cohort
        # branch must literally divide by m for bitwise parity with
        # jnp.mean in average_stacked (see SVSubstrate's masked twin)
        cnt_f = jnp.maximum(cnt, 1).astype(jnp.float32)
        sum_w = jnp.sum(jnp.where(mask[:, None], models.w, 0.0), axis=0)
        sum_b = jnp.sum(jnp.where(mask, models.b, 0.0))
        w = jnp.where(cnt == m, jnp.mean(models.w, axis=0), sum_w / cnt_f)
        b = jnp.where(cnt == m, jnp.mean(models.b), sum_b / cnt_f)
        return cls(w=w, b=b), jnp.zeros((), jnp.float32)

    def sync_payload_masked(self, models, mask, ledger):
        # sync_bytes_linear with the traced cohort size: 2 c |theta| B
        count = jnp.sum(mask.astype(jnp.int32))
        # reprolint: allow[ACC01] int32 mirrors sync_bytes_linear; bounded by the full-m value
        return (count * jnp.asarray(2 * self.num_params * 4, jnp.int32),
                ledger)

    def rejoin_payload_bytes(self, models, ref, rejoin):
        # dense vectors have no identity structure: a rejoin is one
        # full download per recovering learner (linear_payload_bytes)
        # reprolint: allow[ACC01] int32 rejoin count; bounded by m
        count = jnp.sum(rejoin.astype(jnp.int32))
        # reprolint: allow[ACC01] int32 mirrors linear_payload_bytes; bounded by m |theta| B
        return count * jnp.asarray(
            # reprolint: allow[ACC01] int32 mirrors linear_payload_bytes; bounded by m |theta| B
            accounting.linear_payload_bytes(self.num_params), jnp.int32)

    def allreduce_sync_bytes_masked(self, count):
        # allreduce_bytes with traced cohort size: 2 (c-1) |theta| B
        # reprolint: allow[ACC01] int32 mirrors allreduce_bytes; bounded by the full-m value
        return (2 * jnp.maximum(count - 1, 0)
                # reprolint: allow[ACC01] int32 mirrors allreduce_bytes; bounded by the full-m value
                * jnp.asarray(self.num_params * 4, jnp.int32))

    def dist_one(self, model, ref) -> Array:
        return rkhs.rowsum((model.w - ref.w) ** 2) + (model.b - ref.b) ** 2

    def upload_payload(self, bm, state, known):
        return (state, set(),
                accounting.linear_payload_bytes(self.num_params,
                                                bm.dtype_bytes))

    def download_payload_bytes(self, bm, union, receiver_ids):
        return accounting.linear_payload_bytes(self.num_params,
                                               bm.dtype_bytes)

    def aggregate(self, reference, models, weights):
        """Mean over candidates (1 - w_k) r + w_k f_k in weight space."""
        n = len(models)
        assert n == len(weights) and n > 0
        cls = self._state_cls()
        w_acc = np.zeros_like(np.asarray(reference.w, np.float64))
        b_acc = 0.0
        rw = np.asarray(reference.w, np.float64)
        rb = float(reference.b)
        for st, wt in zip(models, weights):
            w_acc += (1.0 - wt) * rw + wt * np.asarray(st.w, np.float64)
            b_acc += (1.0 - wt) * rb + wt * float(st.b)
        return cls(
            w=jnp.asarray((w_acc / n).astype(np.float32)),
            b=jnp.asarray(np.float32(b_acc / n)),
        ), None, set()

    def adopt_node(self, state, fsync):
        cls = self._state_cls()
        return cls(w=fsync.w, b=fsync.b)

    def snapshot_buffers(self, T, m):
        D = int(np.prod(self.init_node(0).w.shape))
        return {"w": np.zeros((T, m, D), np.float32),
                "b": np.zeros((T, m), np.float32)}

    def write_snapshot(self, bufs, t, i, st):
        bufs["w"][t, i] = np.asarray(st.w)
        bufs["b"][t, i] = float(st.b)

    def divergence_series(self, bufs):
        snap_w, snap_b = bufs["w"], bufs["b"]
        wbar = snap_w.mean(axis=1, keepdims=True)      # (T, 1, D)
        bbar = snap_b.mean(axis=1, keepdims=True)      # (T, 1)
        return (((snap_w - wbar) ** 2).sum(-1)
                + (snap_b - bbar) ** 2).mean(axis=1)


# ---------------------------------------------------------------------------
# Linear substrate (the paper's baseline hypothesis class)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinearSubstrate(_PrimalSubstrate):
    """Euclidean weight vectors with fixed-size sync payloads."""

    lcfg: LearnerConfig = dataclasses.field(
        default_factory=lambda: LearnerConfig(algo="linear_sgd"))
    backend: str = "reference"    # accepted for uniformity; no kernel algebra

    def __post_init__(self):
        if self.lcfg.is_kernel:
            raise ValueError("LinearSubstrate needs a linear LearnerConfig")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")

    @property
    def loss(self) -> str:
        return self.lcfg.loss

    @property
    def input_dim(self) -> int:
        return self.lcfg.dim

    @property
    def num_params(self) -> int:
        return self.lcfg.dim + 1

    def _state_cls(self):
        return LinearLearnerState

    def init(self, m: int):
        states = [learners.init_state(self.lcfg, i) for i in range(m)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def predict(self, models, x: Array) -> Array:
        # multiply + fixed-order sum, not a dot — layout-independent
        # floats (rkhs.rowsum has the full rationale; DESIGN.md Sec. 9)
        return rkhs.rowsum(models.w * x) + models.b

    def update(self, state, example):
        return jax.vmap(functools.partial(learners.update, self.lcfg))(
            state, example)

    # linear_sgd's round is exactly the fused primal step with the
    # identity feature map; linear_pa (and the non-engaged / reference
    # cases) keep the composed expressions
    fused_scan_round = True

    def round_stacked(self, state, example):
        x, y = example
        if (self.backend == "pallas" and self.lcfg.algo == "linear_sgd"
                and _kops().engages(x.shape[0], self.lcfg.dim)):
            w_new, b_new, ell, yhat = _kops().fused_primal_step(
                x, y, state.w, state.b, loss=self.loss,
                eta=self.lcfg.eta, lam=self.lcfg.lam)
            return LinearLearnerState(w=w_new, b=b_new), ell, yhat
        yhat = self.predict(state, x)
        new_state, ell = self.update(state, example)
        return new_state, ell, yhat

    def init_node(self, idx: int):
        return learners.init_state(self.lcfg, idx)

    def update_one(self, state, example):
        return learners.update(self.lcfg, state, example)

    def predict_one(self, model, x: Array) -> Array:
        return rkhs.rowsum(model.w * x) + model.b

    def init_reference(self):
        return learners.init_linear_state(self.lcfg)


# ---------------------------------------------------------------------------
# RFF substrate (paper Sec. 4 future work, made first-class)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _rff_consts(spec: RFFSpec) -> Tuple[np.ndarray, np.ndarray]:
    """Host copies of (W, b) so jitted substrate methods embed them as
    constants (hoisted out of the scan body) instead of re-deriving the
    random projection every step.  ``ensure_compile_time_eval`` keeps
    the draw eager even when the first call happens inside a trace."""
    with jax.ensure_compile_time_eval():
        W, b = rff.rff_params(spec)
    return np.asarray(W), np.asarray(b)


@dataclasses.dataclass(frozen=True)
class RFFSubstrate(_PrimalSubstrate):
    """Primal SGD over D random Fourier features.

    The model is a fixed-size weight vector over phi(x) = sqrt(2/D)
    cos(W x + b), so every synchronization ships O(m D) bytes no matter
    how many examples have been seen — the strict adaptivity of Cor. 8
    at near-kernel accuracy (benchmarks/bench_rff.py measures both).
    """

    spec: RFFSpec = dataclasses.field(
        default_factory=lambda: RFFSpec(dim=8, num_features=256))
    eta: float = 0.5
    lam: float = 0.01
    loss: str = "hinge"
    backend: str = "reference"

    def __post_init__(self):
        if self.loss not in ("hinge", "squared"):
            raise ValueError(f"unknown loss {self.loss!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")

    @property
    def input_dim(self) -> int:
        return self.spec.dim

    @property
    def num_params(self) -> int:
        return self.spec.num_features + 1

    def _state_cls(self):
        return RFFLearnerState

    def _phi(self, X2d: Array) -> Array:
        """phi over a batch of rows: (n, d) -> (n, D).  Engage-aware:
        below the Pallas threshold the pallas backend featurizes with
        the reference map, bit-identical to backend="reference"."""
        W, b = _rff_consts(self.spec)
        if self.backend == "pallas" and _kops().engages(
                X2d.shape[0], self.spec.num_features):
            return _kops().rff_features(X2d, jnp.asarray(W), jnp.asarray(b))
        return rff.featurize(self.spec, jnp.asarray(W), jnp.asarray(b), X2d)

    def init(self, m: int):
        states = [rff.init_state(self.spec) for _ in range(m)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def predict(self, models, x: Array) -> Array:
        Z = self._phi(x)                               # (m, D)
        return rkhs.rowsum(models.w * Z) + models.b

    def predict_batch(self, models, lids: Array, Xb: Array) -> Array:
        # featurize the whole bucket in one _phi call (the feature map
        # dominates an RFF predict), then gather each request's home
        # weights; per-row floats match predict_one because featurize
        # and the dot are row-independent multiply+reduce ops.
        Z = self._phi(Xb)                              # (n, D)
        return rkhs.rowsum(models.w[lids] * Z) + models.b[lids]

    def _round_with_features(self, st, z, y):
        yhat = rkhs.rowsum(st.w * z) + st.b   # layout-independent floats
        ell, g = learners.loss_and_grad(self.loss, yhat, y)
        w = (1.0 - self.eta * self.lam) * st.w - self.eta * g * z
        b = st.b - self.eta * g
        return RFFLearnerState(w=w, b=b), ell, yhat

    def _update_with_features(self, st, z, y):
        new_state, ell, _ = self._round_with_features(st, z, y)
        return new_state, ell

    def update(self, state, example):
        x, y = example
        Z = self._phi(x)                               # (m, D)
        return jax.vmap(self._update_with_features)(state, Z, y)

    # the whole stacked round — featurize + predict + loss/grad +
    # NORMA update — as one computation; under an engaged pallas
    # backend it is ONE kernel launch (kernels.ops.fused_primal_step)
    fused_scan_round = True

    def round_stacked(self, state, example):
        x, y = example
        if self.backend == "pallas" and _kops().engages(
                x.shape[0], self.spec.num_features):
            W, b = _rff_consts(self.spec)
            w_new, b_new, ell, yhat = _kops().fused_primal_step(
                x, y, state.w, state.b,
                W=jnp.asarray(W), bias=jnp.asarray(b),
                scale=float(np.sqrt(2.0 / self.spec.num_features)),
                loss=self.loss, eta=self.eta, lam=self.lam)
            return RFFLearnerState(w=w_new, b=b_new), ell, yhat
        # unfused: one shared featurize (instead of the composed
        # path's two), the exact predict expression, the exact update
        Z = self._phi(x)                               # (m, D)
        yhat = rkhs.rowsum(state.w * Z) + state.b
        new_state, ell = jax.vmap(self._update_with_features)(state, Z, y)
        return new_state, ell, yhat

    def init_node(self, idx: int):
        return rff.init_state(self.spec)

    def update_one(self, state, example):
        x, y = example
        z = self._phi(x[None])[0]
        return self._update_with_features(state, z, y)

    def predict_one(self, model, x: Array) -> Array:
        z = self._phi(x[None])[0]
        return rkhs.rowsum(model.w * z) + model.b

    # the feature map dominates a node round: featurize once, share it
    # between the service-error prediction and the update
    fused_node_round = True

    def round_one(self, state, example):
        x, y = example
        z = self._phi(x[None])[0]
        return self._round_with_features(state, z, y)

    def init_reference(self):
        return rff.init_state(self.spec)


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def substrate_of(
    learner,
    *,
    sync_budget: Optional[int] = None,
    compress_method: Optional[str] = None,
    backend: Optional[str] = None,
) -> Substrate:
    """Resolve a learner description to a Substrate.

    Accepts a :class:`Substrate` (returned as-is, except that keyword
    arguments explicitly passed — the defaults are ``None`` sentinels,
    so every explicit value counts, including "reference"/"truncate" —
    are applied via ``dataclasses.replace``; ``engine.run(sub, ...,
    backend="pallas")`` does what it says), a :class:`LearnerConfig`
    (kernel algos -> :class:`SVSubstrate`, linear algos ->
    :class:`LinearSubstrate`; representation-inapplicable keywords are
    resolved away exactly as the legacy drivers did), or an
    :class:`RFFSpec` (-> :class:`RFFSubstrate` with the default SGD
    hyperparameters).  An override the resolved substrate type has no
    field for raises ValueError rather than being dropped.

    ``None`` semantics of the keyword sentinels: ``None`` means "keep
    the substrate's own configuration" — for a passed :class:`Substrate`
    that is whatever it was built with; for a :class:`LearnerConfig` /
    :class:`RFFSpec` it is the dataclass default, i.e.
    ``compress_method=None`` resolves to
    ``compression.DEFAULT_METHOD`` ("truncate"), ``backend=None`` to
    "reference", and ``sync_budget=None`` to the learner budget tau.
    """
    overrides = {}
    if sync_budget is not None:
        overrides["sync_budget"] = int(sync_budget)
    if compress_method is not None:
        overrides["compress_method"] = compress_method
    if backend is not None:
        overrides["backend"] = backend

    if isinstance(learner, Substrate):
        if not overrides:
            return learner
        sub = learner
    elif isinstance(learner, LearnerConfig):
        if learner.is_kernel:
            return SVSubstrate(
                lcfg=learner,
                sync_budget=int(sync_budget or learner.budget),
                compress_method=compress_method or compression.DEFAULT_METHOD,
                backend=backend or "reference")
        # linear models have no sync budget / compression: the legacy
        # drivers accepted and ignored these, so the resolver does too
        return LinearSubstrate(lcfg=learner, backend=backend or "reference")
    elif isinstance(learner, RFFSpec):
        sub = RFFSubstrate(spec=learner)
        if not overrides:
            return sub
    else:
        raise TypeError(
            f"cannot build a substrate from {type(learner).__name__}; pass a "
            "Substrate, LearnerConfig, or RFFSpec")

    fields = {f.name for f in dataclasses.fields(sub)}
    unknown = sorted(set(overrides) - fields)
    if unknown:
        raise ValueError(
            f"{unknown} cannot be applied to {type(sub).__name__}; "
            "configure the substrate directly")
    return dataclasses.replace(sub, **overrides)
