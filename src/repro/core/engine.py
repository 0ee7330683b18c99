"""Device-resident scan simulation engine (DESIGN.md Sec. 7).

``simulation.run_kernel_simulation`` drives the m-learner system with a
Python loop: every round costs several jitted dispatches plus a host
round-trip (``float()`` on losses / divergence) and a numpy set-algebra
pass per sync.  This module compiles the ENTIRE T-round experiment into
one ``jax.lax.scan``: the carry holds (stacked learner states,
reference model, device byte ledger), every per-round observable
(loss, errors, bytes, divergence, sync flag, compression eps) comes
back as a T-length output array, and the host touches data exactly once
at the end.

There is ONE scan core.  Everything representation-specific — how a
model predicts, updates, averages, measures distance to the reference,
and what a synchronization costs in Sec. 3 bytes — lives behind the
``core.substrate.Substrate`` interface (DESIGN.md Sec. 8), so the same
compiled step serves support-vector expansions (``SVSubstrate`` with
the jit-resident ``accounting.DeviceLedger`` set algebra), random
Fourier feature models (``RFFSubstrate``: fixed O(m D) bytes per sync),
and the paper's linear baselines (``LinearSubstrate``).  ``run`` /
``sweep`` accept a ``LearnerConfig`` (resolved via
``substrate.substrate_of``), an ``RFFSpec``, or a ``Substrate``.

``sweep`` vmaps the whole simulation across a grid of ProtocolConfigs
(delta / period / mini_batch) and optionally per-config data streams
(seeds), one compilation per (substrate, protocol kind) — the
grid-evaluation workload of Kamp et al.'s adaptive-bounds protocol
family, including mixed-substrate grids (e.g. SV vs RFF vs linear on
the same stream).

Mesh-sharded execution (DESIGN.md Sec. 9): ``run(..., mesh=...)`` /
``sweep(..., mesh=...)`` execute the SAME scan core with the learner
axis sharded across a real ``jax.sharding.Mesh`` via ``shard_map``.
Learner state, streams, and the Sec. 3 stacked reference live sliced
per device; ``predict`` / ``update`` / the dynamic local-condition
distance are purely device-local, the protocol's only unconditional
cross-device traffic is the one-bit violation all-reduce, and a
synchronization lowers to an ``all_gather`` of the stacked models (the
sorted-id arrays feeding ``DeviceLedger`` ride along) followed by a
replicated average + local adopt.  The sharded engine reproduces the
single-device engine bit-for-bit on losses and integer-exactly on the
byte ledger (tests/test_engine_mesh.py, on 8 forced host devices).

Topology accounting: ``topology="coordinator"`` (default) charges the
paper's Sec. 3 designated-coordinator bytes; ``topology="allreduce"``
charges the mesh collective instead (``accounting.allreduce_bytes`` /
``allgather_bytes`` ring totals via ``Substrate.allreduce_sync_bytes``)
— same sync decisions, same models, different price — so every
experiment can report both topologies side by side.  The switch works
with and without a mesh.

Static vs. traced configuration: the protocol ``kind`` and the
substrate change the structure of the scan body (what is computed each
round), so they are compile-time specializations; ``delta``, ``period``
and ``mini_batch`` are traced scalars, so one compiled executable
serves a whole grid.

Exactness contract against the legacy serial driver:

- ``cumulative_bytes``, ``sync_rounds``, ``num_syncs`` are
  integer-exact;
- per-learner per-round losses / errors are the same float32 values;
  the cross-learner sum runs on the host (numpy, one fixed reduction
  order for every execution mode — the legacy driver sums on device,
  so per-round sums agree to float32 rounding and error counts agree
  exactly), then accumulates in float64 exactly like the legacy
  driver's accumulators;
- the RKHS divergence series delta(f_t) is the one observable whose
  *recording* costs a full union Gram every round, and nothing in the
  protocol consumes it — so it is opt-in (``record_divergence=True``;
  substrates with ``free_divergence`` — linear, RFF — always record it,
  the cost there is O(m d)).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import substrate as substrate_mod
from .learners import LearnerConfig
from .protocol import PROTOCOL_KIND_CODES, ProtocolConfig
from .simulation import SimResult
from .substrate import Substrate
# probe imports only jax, so this holds whichever of repro.core and
# repro.telemetry (whose monitor imports repro.core) is imported first
from ..telemetry import probe

Array = jnp.ndarray

LearnerLike = Union[Substrate, LearnerConfig, "substrate_mod.RFFSpec"]

TOPOLOGIES = ("coordinator", "allreduce")


class ScanParams(NamedTuple):
    """The traced protocol parameters of one simulation (scalars), or of
    a sweep (vectors of length n_configs)."""

    delta: Array
    period: Array
    mini_batch: Array


def params_of(pcfg: ProtocolConfig) -> ScanParams:
    """The traced-scalar view of one ProtocolConfig — the companion of
    :func:`make_protocol_step`, so external step drivers (the serving
    engine) share the scan engine's exact dtype conversion."""
    return ScanParams(
        delta=jnp.asarray(pcfg.delta, jnp.float32),
        period=jnp.asarray(pcfg.period, jnp.int32),
        mini_batch=jnp.asarray(pcfg.mini_batch, jnp.int32),
    )


_params_of = params_of


def _stack_params(pcfgs: Sequence[ProtocolConfig]) -> ScanParams:
    return ScanParams(
        delta=jnp.asarray([p.delta for p in pcfgs], jnp.float32),
        period=jnp.asarray([p.period for p in pcfgs], jnp.int32),
        mini_batch=jnp.asarray([p.mini_batch for p in pcfgs], jnp.int32),
    )


def _err_terms(loss: str, yhat: Array, y: Array) -> Array:
    """Per-learner service-error terms (prediction mistakes for hinge,
    squared error otherwise).  The hinge decision rule is deterministic
    at a zero margin — ``yhat >= 0`` predicts +1 — so an untrained
    all-zero model is scored against one label, not both; the serial
    oracle (core/simulation.py) and the async runtime nodes apply the
    identical rule."""
    if loss == "hinge":
        return (jnp.where(yhat >= 0, 1.0, -1.0) != y).astype(jnp.float32)
    return (yhat - y) ** 2


# ---------------------------------------------------------------------------
# The one generic scan core, parameterized by substrate
# ---------------------------------------------------------------------------


def _allreduce_cost(sub: Substrate, m: int) -> Array:
    """Trace-time constant ring bytes of one sync, int32-guarded like
    the device ledger (accounting.device_sync_bytes_kernel)."""
    cost = int(sub.allreduce_sync_bytes(m))
    if cost >= 2**31:
        raise ValueError(
            f"per-sync ring bytes {cost} for m={m} overflow the byte "
            "ledger's int32; use the host accounting at this scale")
    return jnp.asarray(cost, jnp.int32)


def _tree_select(mask: Array, new, old):
    """Per-learner select over a stacked state tree: leaf shapes are
    (m, ...), ``mask`` is (m,) bool — broadcast against the trailing
    dims.  ``jnp.where`` on identical operands is the identity, so an
    all-True mask keeps the masked engine bitwise on the unmasked path."""
    def sel(n, o):
        return jnp.where(mask.reshape(mask.shape + (1,) * (n.ndim - 1)),
                         n, o)
    return jax.tree.map(sel, new, old)


def _make_step(sub: Substrate, kind: str, record_divergence: bool,
               topology: str, axis, masked: bool = False):
    """One scan step over (state, reference, ledger).

    ``axis=None`` is the single-device engine: ``reference`` is ONE
    synchronized model and every reduction sees all m learners.

    ``axis`` set means the step runs inside ``shard_map`` with the
    learner dim sharded over the named mesh axes (DESIGN.md Sec. 9):
    state / streams / reference are per-device slices, ``reference``
    carries a leading (local) learner axis — the Sec. 3 stacked
    reference — and the cross-device protocol is exactly (a) the
    one-bit violation all-reduce of the dynamic check and (b) an
    ``all_gather`` of the stacked models when a sync fires.  The
    loss/err observables stay PER-LEARNER (sharded outputs, summed on
    the host identically in both modes): a device-side cross-learner
    sum would make the recorded floats depend on the reduction order
    the compiler picks for that program, which is exactly the
    bit-for-bit leak the parity contract forbids.

    ``masked`` (DESIGN.md Sec. 15) threads a per-round participation
    mask: ``xs`` gains a (m,) bool row ``p`` and the carry gains the
    previous round's mask.  Inactive learners keep their state bitwise
    (no predict/update), report zero loss/err, contribute nothing to
    the violation check or the sync average, and pay no bytes.
    Learners with ``p & ~prev`` are RE-JOINING after churn: before
    their first round back they re-``adopt`` the current reference and
    the ledger is charged the Sec. 3 download
    (``Substrate.rejoin_payload_bytes``).  A round with an empty cohort
    syncs nothing and moves zero bytes.  With an all-True mask every
    ``jnp.where`` selects the unmasked operand, so this path reproduces
    the unmasked step bit-for-bit (tests/test_population.py).
    """
    sharded = axis is not None

    def gather_tree(t):
        if not sharded:
            return t
        return jax.tree.map(
            lambda v: lax.all_gather(v, axis, axis=0, tiled=True), t)

    def step(params: ScanParams, carry, xs):
        if masked:
            state, reference, ledger, prev = carry
            x, y, t, p = xs
            cohort = jnp.sum(p.astype(jnp.int32))
            n_rejoin = jnp.sum((p & jnp.logical_not(prev)).astype(jnp.int32))
            if sharded:
                cohort = lax.psum(cohort, axis)
                n_rejoin = lax.psum(n_rejoin, axis)
                m_total = lax.psum(jnp.asarray(p.shape[0], jnp.int32), axis)
            else:
                m_total = p.shape[0]
            any_active = cohort > 0
            all_active = cohort == m_total
            # churn recovery: a rejoining learner (p & ~prev) downloads
            # the current reference before its first round back.  The
            # whole phase lives behind a lax.cond so that rejoin-free
            # rounds — every round of a full-participation run — take
            # an identity branch: inlining the rejoin selects into the
            # scan body changes how XLA fuses the predict/update
            # cluster and drifts full-participation floats by ulps
            # (the cond compiles branches as separate computations).
            rejoin = p & jnp.logical_not(prev)
            ref_one = (jax.tree.map(lambda v: v[0], reference)
                       if sharded else reference)

            def do_rejoin(models):
                rjb = sub.rejoin_payload_bytes(models, ref_one, rejoin)
                if sharded:
                    rjb = lax.psum(rjb, axis)
                new = _tree_select(
                    rejoin, sub.adopt(models, ref_one), models)
                return new, jnp.asarray(rjb, jnp.int32)

            def no_rejoin(models):
                return models, jnp.zeros((), jnp.int32)

            models, rejoin_bytes = lax.cond(
                n_rejoin > 0, do_rejoin, no_rejoin, sub.models_of(state))
            state = sub.with_models(state, models)
        else:
            state, reference, ledger = carry
            x, y, t = xs
        pre_state = state

        with jax.named_scope(probe.SCOPE_PREDICT_UPDATE):
            if sub.fused_scan_round:
                # one fused round: predict + update share their featurize/
                # Gram work (and under an engaged pallas backend run as a
                # single kernel launch) — core/substrate.py round_stacked
                state, losses, yhat = sub.round_stacked(state, (x, y))
            else:
                yhat = sub.predict(sub.models_of(state), x)
                state, losses = sub.update(state, (x, y))
            err = _err_terms(sub.loss, yhat, y)     # per-learner
        if masked:
            # inactive learners: no round happened — state stays as the
            # (possibly rejoin-adopted) pre-round state, observables
            # zero.  Same cond discipline as the rejoin phase: a
            # full-cohort round takes the identity branch, keeping the
            # masking selects out of the round's HLO cluster.
            def apply_mask(args):
                state, losses, err = args
                return (_tree_select(p, state, pre_state),
                        jnp.where(p, losses, 0.0),
                        jnp.where(p, err, 0.0))

            state, losses, err = lax.cond(
                all_active, lambda args: args, apply_mask,
                (state, losses, err))
        models = sub.models_of(state)

        if kind == "none":
            do_sync = jnp.zeros((), bool)
        elif kind == "continuous":
            do_sync = any_active if masked else jnp.ones((), bool)
        elif kind == "periodic":
            do_sync = ((t + 1) % params.period) == 0
            if masked:
                do_sync = do_sync & any_active
        else:  # dynamic: check local conditions every mini_batch rounds
            check_now = ((t + 1) % params.mini_batch) == 0

            def check(_):
                if sharded:
                    dists = sub.dist_to_ref_each(models, reference)
                else:
                    dists = sub.dist_to_ref(models, reference)
                violations = dists > params.delta
                if masked:
                    # only the participating cohort is polled; stale
                    # detached models cannot trigger a sync
                    violations = p & violations
                return jnp.any(violations)

            with jax.named_scope(probe.SCOPE_CHECK):
                if sub.guarded_dist_check:
                    # the distance costs a Gram — only pay it on check
                    # rounds (lax.cond skips the untaken branch)
                    violated = lax.cond(check_now, check,
                                        lambda _: jnp.zeros((), bool), None)
                else:
                    violated = check_now & check(None)
                if sharded:
                    # the one-bit violation all-reduce: the only
                    # unconditional cross-device traffic of the protocol
                    do_sync = lax.psum(violated.astype(jnp.int32), axis) > 0
                else:
                    do_sync = violated

        if kind == "none":
            new_models, new_ref, new_ledger = models, reference, ledger
            nbytes = jnp.zeros((), jnp.int32)
            eps = jnp.zeros((), jnp.float32)
        else:

            def sync_branch(args):
                models, reference, ledger = args
                full = gather_tree(models)
                if masked:
                    full_mask = gather_tree(p)
                    fsync, eps = sub.average_stacked_masked(full, full_mask)
                    if topology == "coordinator":
                        nbytes, new_ledger = sub.sync_payload_masked(
                            full, full_mask, ledger)
                    else:
                        # static full-m guard, traced cohort-sized cost
                        _allreduce_cost(
                            sub, jax.tree.leaves(full)[0].shape[0])
                        nbytes = sub.allreduce_sync_bytes_masked(cohort)
                        new_ledger = ledger
                    # only the cohort adopts; detached learners stay on
                    # their stale model until they rejoin
                    new_models = _tree_select(
                        p, sub.adopt(models, fsync), models)
                else:
                    fsync, eps = sub.average_stacked(full)
                    if topology == "coordinator":
                        nbytes, new_ledger = sub.sync_payload(full, ledger)
                    else:
                        m = jax.tree.leaves(full)[0].shape[0]
                        nbytes, new_ledger = _allreduce_cost(sub, m), ledger
                    new_models = sub.adopt(models, fsync)
                if sharded:
                    m_local = jax.tree.leaves(models)[0].shape[0]
                    new_ref = _stack_ref(fsync, m_local)
                else:
                    new_ref = fsync
                return (new_models, new_ref, new_ledger,
                        jnp.asarray(nbytes, jnp.int32),
                        jnp.asarray(eps, jnp.float32))

            def keep_branch(args):
                models, reference, ledger = args
                return (models, reference, ledger,
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.float32))

            with jax.named_scope(probe.SCOPE_SYNC):
                new_models, new_ref, new_ledger, nbytes, eps = lax.cond(
                    do_sync, sync_branch, keep_branch,
                    (models, reference, ledger))

        state = sub.with_models(state, new_models)
        if record_divergence or sub.free_divergence:
            div = sub.divergence(gather_tree(sub.models_of(state)))
        else:
            div = jnp.zeros((), jnp.float32)
        if masked:
            nbytes = nbytes + rejoin_bytes
            out = (losses, err, nbytes, div, do_sync, eps)
            return (state, new_ref, new_ledger, p), out
        out = (losses, err, nbytes, div, do_sync, eps)
        return (state, new_ref, new_ledger), out

    return step


def _stack_ref(ref, m: int):
    """Broadcast one synchronized model to a leading learner axis — the
    Sec. 3 stacked reference, one slice per learner."""
    return jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (m,) + v.shape), ref)


def make_protocol_step(sub: Substrate, kind: str, *,
                       record_divergence: bool = False,
                       topology: str = "coordinator"):
    """One protocol round as a standalone function — EXACTLY the scan
    body ``run`` / ``sweep`` iterate.

    Returns ``step(params, carry, xs) -> (carry, outs)`` with
    ``carry = (stacked learner state, reference, ledger)``,
    ``xs = (x (m, d), y (m,), t int32)`` and
    ``outs = (loss (m,), err (m,), bytes, divergence, sync_flag, eps)``.

    The online serving engine (repro/serving, DESIGN.md Sec. 10) jits
    this step and drives it one labeled round at a time between predict
    micro-batches: because it is the same function object the scan
    engine compiles, the serving path's losses, sync decisions, and
    Sec. 3 bytes are bit-identical to ``run`` by construction — the
    same already-proven discipline by which the serial loop driver
    (core/simulation.py) matches the scan engine while composing
    separately-jitted per-round ops.
    """
    if kind not in PROTOCOL_KIND_CODES:
        raise ValueError(f"unknown protocol kind {kind!r}")
    if topology not in TOPOLOGIES:
        raise ValueError(f"unknown topology {topology!r}")
    return _make_step(sub, kind, record_divergence, topology, axis=None)


def init_protocol_carry(sub: Substrate, m: int):
    """The round-0 scan carry of an m-learner system: freshly
    initialized stacked learner states, the compressed average of those
    blank models as the first reference, and an empty byte ledger —
    shared by the scan core and the serving engine so both start from
    the identical state."""
    state0 = sub.init(m)
    ref0, _ = sub.average_stacked(sub.models_of(state0))
    return state0, ref0, sub.ledger_init(m)


def _row_sums(a: np.ndarray) -> np.ndarray:
    """Sum over the last (learner) axis in one order whatever the
    array's memory order (:func:`assemble_sim_result`)."""
    return np.ascontiguousarray(a).sum(axis=-1)


def assemble_sim_result(sub: Substrate, record_divergence: bool,
                        loss: np.ndarray, err: np.ndarray,
                        round_bytes: np.ndarray, div: np.ndarray,
                        flags: np.ndarray, eps: np.ndarray) -> SimResult:
    """Host-side post-processing of per-round step outputs — ONE code
    path for :func:`run` and the serving engine's ``result()``.

    ``loss`` / ``err`` arrive PER-LEARNER as (T, m) float32; the
    cross-learner sum happens HERE, identically for every execution
    mode — numpy's pairwise float32 sum over identical per-learner
    values — which is what makes the mesh-sharded engine and the
    serving path bit-for-bit with the single-device scan.  numpy's
    summation order follows the array's memory order, and a device
    array can arrive column-major (the TPU returns the scan's (T, m)
    outputs that way, while the serving path stacks rows), so the sum
    runs over a row-major copy.  Divergence
    and eps series are dropped when not recorded / not produced,
    matching the substrate's ``free_divergence`` / ``has_eps`` flags.
    """
    keep_div = record_divergence or sub.free_divergence
    return SimResult.from_round_series(
        _row_sums(loss), _row_sums(err), round_bytes,
        div if keep_div else np.zeros((0,)),
        flags,
        eps if sub.has_eps else np.zeros((0,)))


def _scan_core(sub: Substrate, kind: str, record_divergence: bool,
               topology: str = "coordinator", masked: bool = False):
    step = _make_step(sub, kind, record_divergence, topology, axis=None,
                      masked=masked)

    if masked:
        def simulate(params: ScanParams, X: Array, Y: Array, part: Array):
            T, m, d = X.shape
            state0, ref0, ledger0 = init_protocol_carry(sub, m)
            # prev-mask carry starts as round 0's mask: nobody is
            # "rejoining" into the freshly distributed blank reference
            carry0 = (state0, ref0, ledger0, part[0])
            ts = jnp.arange(T, dtype=jnp.int32)
            _, outs = lax.scan(functools.partial(step, params),
                               carry0, (X, Y, ts, part))
            return outs

        return simulate

    def simulate(params: ScanParams, X: Array, Y: Array):
        T, m, d = X.shape
        carry0 = init_protocol_carry(sub, m)
        ts = jnp.arange(T, dtype=jnp.int32)
        _, outs = lax.scan(functools.partial(step, params),
                           carry0, (X, Y, ts))
        return outs

    return simulate


# ---------------------------------------------------------------------------
# Mesh-sharded core (DESIGN.md Sec. 9)
# ---------------------------------------------------------------------------


def learner_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes the learner dim is sharded over: the ``learners``
    axis when the mesh has one (``launch.mesh.make_learner_mesh``),
    otherwise every axis except ``model`` (the convention of
    DESIGN.md Sec. 5)."""
    if "learners" in mesh.axis_names:
        return ("learners",)
    axes = tuple(a for a in mesh.axis_names if a != "model")
    if not axes:
        raise ValueError(
            f"mesh {mesh.axis_names} has no learner axis; name one "
            "'learners' or include a non-'model' axis")
    return axes


def _num_shards(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _sharded_core(sub: Substrate, kind: str, record_divergence: bool,
                  topology: str, mesh: Mesh, axes: Tuple[str, ...],
                  vmapped: bool, data_batched: bool,
                  masked: bool = False):
    """The scan core under ``shard_map``: learner axis sharded over
    ``axes``, config axis (when ``vmapped``) vmapped INSIDE the shard
    so one mesh program serves the whole grid.

    Layout (in_specs): learner state, streams and the stacked
    reference are sharded on their learner dim; protocol params and
    the DeviceLedger are replicated (the ledger is the coordinator's
    cache — every device maintains the identical copy from the
    gathered union, so the coordinator-topology accounting needs no
    host).  Outputs: the per-learner loss/err series come back sharded
    like the streams; bytes / divergence / sync flags / eps are
    replicated per-round scalars.
    """
    if masked and vmapped:
        raise NotImplementedError(
            "participation masks are per-run (engine.run); sweep grids "
            "do not take a participation= argument")
    step = _make_step(sub, kind, record_divergence, topology, axis=axes,
                      masked=masked)

    if masked:
        def local_run(params: ScanParams, state0, ref0, ledger0, X, Y,
                      part):
            T = X.shape[0]
            ts = jnp.arange(T, dtype=jnp.int32)
            _, outs = lax.scan(functools.partial(step, params),
                               (state0, ref0, ledger0, part[0]),
                               (X, Y, ts, part))
            return outs
    else:
        def local_run(params: ScanParams, state0, ref0, ledger0, X, Y):
            T = X.shape[0]
            ts = jnp.arange(T, dtype=jnp.int32)
            _, outs = lax.scan(functools.partial(step, params),
                               (state0, ref0, ledger0), (X, Y, ts))
            return outs

    body = local_run
    if vmapped:
        dax = 0 if data_batched else None
        body = jax.vmap(local_run,
                        in_axes=(ScanParams(0, 0, 0), None, None, None,
                                 dax, dax))

    lead = axes if len(axes) > 1 else axes[0]
    data_spec = P(None, None, lead) if (vmapped and data_batched) \
        else P(None, lead)
    # per-learner loss/err series come back sharded like the streams;
    # bytes / divergence / flags / eps are replicated per-round scalars
    series_spec = P(None, None, lead) if vmapped else P(None, lead)
    scalar_spec = P()
    in_specs = (P(), P(lead), P(lead), P(), data_spec, data_spec)
    if masked:
        in_specs = in_specs + (P(None, lead),)   # participation (T, m)
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=(series_spec, series_spec, scalar_spec, scalar_spec,
                   scalar_spec, scalar_spec),
        check_vma=False)

    if masked:
        def simulate(params: ScanParams, X: Array, Y: Array, part: Array):
            m = X.shape[1]
            state0 = sub.init(m)
            ref0, _ = sub.average_stacked(sub.models_of(state0))
            ledger0 = sub.ledger_init(m)
            return smapped(params, state0, _stack_ref(ref0, m), ledger0,
                           X, Y, part)

        return simulate

    def simulate(params: ScanParams, X: Array, Y: Array):
        m = X.shape[2] if (vmapped and data_batched) else X.shape[1]
        state0 = sub.init(m)
        ref0, _ = sub.average_stacked(sub.models_of(state0))
        ledger0 = sub.ledger_init(m)
        return smapped(params, state0, _stack_ref(ref0, m), ledger0, X, Y)

    return simulate


# ---------------------------------------------------------------------------
# Compiled-function cache and public API
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted(sub: Substrate, kind: str, record_divergence: bool,
            vmapped: bool, data_batched: bool,
            topology: str = "coordinator",
            mesh: Optional[Mesh] = None,
            axes: Optional[Tuple[str, ...]] = None,
            masked: bool = False):
    """One jitted (optionally vmapped / mesh-sharded) simulate fn per
    static config.

    The cache is what lets benchmarks call ``run`` in a timing loop
    without re-tracing: jax.jit caches on function identity, so the
    closure must be built once per static configuration.  Substrates
    are frozen dataclasses (and Meshes are hashable), so they key the
    cache directly.
    """
    if mesh is not None:
        return jax.jit(_sharded_core(
            sub, kind, record_divergence, topology, mesh, axes,
            vmapped, data_batched, masked))
    core = _scan_core(sub, kind, record_divergence, topology, masked)
    if vmapped:
        dax = 0 if data_batched else None
        core = jax.vmap(core, in_axes=(ScanParams(0, 0, 0), dax, dax))
    return jax.jit(core)


def _resolve_mesh(mesh: Optional[Mesh], topology: str, m: int):
    """Validate (mesh, topology) for a run over m learners; returns
    the learner axes (None without a mesh)."""
    if topology not in TOPOLOGIES:
        raise ValueError(
            f"unknown topology {topology!r}; expected one of {TOPOLOGIES}")
    if mesh is None:
        return None
    axes = learner_axes_of(mesh)
    n = _num_shards(mesh, axes)
    if m % n:
        raise ValueError(
            f"{m} learners cannot shard evenly over {n} devices "
            f"(mesh axes {axes})")
    return axes


def run(
    learner: LearnerLike,
    pcfg: ProtocolConfig,
    X: np.ndarray,          # (T, m, d)
    Y: np.ndarray,          # (T, m)
    *,
    sync_budget: Optional[int] = None,
    compress_method: Optional[str] = None,   # None -> substrate's own
    record_divergence: bool = False,
    backend: Optional[str] = None,           # None -> substrate's own
    mesh: Optional[Mesh] = None,
    topology: str = "coordinator",
    participation: Optional[np.ndarray] = None,   # (T, m) bool
) -> SimResult:
    """Run T rounds of m learners under pcfg, fully on device.

    ``learner`` is a Substrate, a LearnerConfig, or an RFFSpec (see
    ``substrate.substrate_of`` — explicitly passed keywords override a
    Substrate's own configuration).  Drop-in replacement for
    ``simulation.run_kernel_simulation`` / ``run_linear_simulation``
    with the exactness contract in the module docstring.

    ``compress_method=None`` (like ``backend=None`` / the other
    keyword sentinels) means "keep the substrate's own configuration":
    for a passed Substrate, whatever it was built with; for a
    LearnerConfig, the dataclass default
    ``SVSubstrate.compress_method == compression.DEFAULT_METHOD``
    ("truncate").  Pass an explicit string ("truncate" | "project") to
    override either way.

    ``mesh``: a ``jax.sharding.Mesh`` to shard the learner axis over
    (``launch.mesh.make_learner_mesh``; m must divide evenly) — same
    losses and ledger as the single-device engine, bit-for-bit.
    ``topology``: "coordinator" charges the paper's Sec. 3 bytes,
    "allreduce" the mesh collective's ring total (DESIGN.md Sec. 9);
    decisions and models are identical either way.

    ``participation``: a (T, m) bool mask selecting the per-round
    cohort (DESIGN.md Sec. 15).  Inactive learners skip predict/update,
    contribute nothing to the violation check or the sync average, and
    pay no Sec. 3 bytes; a learner whose mask flips False→True is
    re-joining after churn and re-``adopt``s the current reference,
    paying the download.  ``participation=None`` (default) and an
    all-True mask both produce the exact unmasked result — losses
    bitwise, bytes integer-exact (tests/test_population.py).
    """
    with jax.profiler.TraceAnnotation(probe.ENGINE_RUN):
        sub = substrate_mod.substrate_of(
            learner, sync_budget=sync_budget,
            compress_method=compress_method, backend=backend)
        if not isinstance(X, jax.Array):   # keep pre-sharded streams on device
            X = np.asarray(X)
        T, m, d = X.shape
        sub.validate(T, m, d)
        axes = _resolve_mesh(mesh, topology, m)
        masked = participation is not None
        if masked:
            part = np.asarray(participation)
            if part.shape != (T, m):
                raise ValueError(
                    f"participation shape {part.shape} != (T, m) = {(T, m)}")
        fn = _jitted(sub, pcfg.kind, bool(record_divergence), False, False,
                     topology, mesh, axes, masked)
        with jax.profiler.TraceAnnotation(probe.ENGINE_UPLOAD):
            args = (_params_of(pcfg), jnp.asarray(X), jnp.asarray(Y))
            if masked:
                args += (jnp.asarray(part.astype(bool)),)
        with jax.profiler.TraceAnnotation(probe.ENGINE_DISPATCH):
            outs = fn(*args)
        with jax.profiler.TraceAnnotation(probe.ENGINE_COPY_BACK):
            loss, err, nbytes, div, flags, eps = [np.asarray(o) for o in outs]
        with jax.profiler.TraceAnnotation(probe.ENGINE_ASSEMBLE):
            return assemble_sim_result(sub, bool(record_divergence),
                                       loss, err, nbytes, div, flags, eps)


@dataclasses.dataclass
class SweepResult:
    """Stacked per-round series of a protocol-grid sweep.

    Every array carries a leading axis of size n = len(configs);
    ``sweep_result[i]`` materializes the i-th configuration as a
    regular ``SimResult``.
    """

    configs: List[ProtocolConfig]
    losses: np.ndarray        # (n, T)
    errors: np.ndarray        # (n, T)
    round_bytes: np.ndarray   # (n, T)
    sync_flags: np.ndarray    # (n, T) bool
    divergences: Optional[np.ndarray]  # (n, T) or None (not recorded)
    eps: Optional[np.ndarray]          # (n, T) or None (eps-free substrates)

    def __len__(self) -> int:
        return len(self.configs)

    def __getitem__(self, i: int) -> SimResult:
        return SimResult.from_round_series(
            self.losses[i], self.errors[i], self.round_bytes[i],
            self.divergences[i] if self.divergences is not None
            else np.zeros((0,)),
            self.sync_flags[i],
            self.eps[i] if self.eps is not None else np.zeros((0,)))

    @property
    def results(self) -> List[SimResult]:
        return [self[i] for i in range(len(self))]


def sweep(
    learner: Union[LearnerLike, Sequence[LearnerLike]],
    pcfgs: Sequence[ProtocolConfig],
    X: np.ndarray,          # (T, m, d) shared, or (n, T, m, d) per config
    Y: np.ndarray,          # (T, m) shared, or (n, T, m)
    *,
    sync_budget: Optional[int] = None,
    compress_method: Optional[str] = None,   # None -> substrate's own
    record_divergence: bool = False,
    backend: Optional[str] = None,           # None -> substrate's own
    mesh: Optional[Mesh] = None,
    topology: str = "coordinator",
) -> SweepResult:
    """Simulate a grid of protocol configurations in one compilation.

    The whole simulation (scan over T rounds, ledger included) is
    vmapped across the config axis; configs are grouped by
    (substrate, kind) so each group shares one compiled executable
    regardless of its delta / period / mini_batch values.  ``learner``
    may also be a sequence of per-config substrates (same length as
    ``pcfgs``) for mixed-substrate grids — e.g. SV vs RFF vs linear on
    the same stream.  Pass X with a leading config axis to sweep seeds
    (per-config data streams) at the same time.

    With ``mesh`` the config axis stays vmapped while the learner axis
    is sharded (the vmap runs inside the ``shard_map``, so the whole
    grid is still one mesh program per (substrate, kind) group);
    ``topology`` selects the byte accounting as in :func:`run`, and
    ``compress_method=None`` / ``backend=None`` keep each substrate's
    own configuration exactly as :func:`run` documents.
    """
    pcfgs = list(pcfgs)
    n = len(pcfgs)
    if n == 0:
        raise ValueError("sweep needs at least one ProtocolConfig")
    if isinstance(learner, (list, tuple)):
        if len(learner) != n:
            raise ValueError(
                f"{len(learner)} substrates != {n} protocol configs")
        subs = [substrate_mod.substrate_of(
            s, sync_budget=sync_budget, compress_method=compress_method,
            backend=backend) for s in learner]
    else:
        one = substrate_mod.substrate_of(
            learner, sync_budget=sync_budget, compress_method=compress_method,
            backend=backend)
        subs = [one] * n
    X = np.asarray(X)
    Y = np.asarray(Y)
    data_batched = X.ndim == 4
    if data_batched and X.shape[0] != n:
        raise ValueError(
            f"per-config data axis {X.shape[0]} != n_configs {n}")
    T = X.shape[1] if data_batched else X.shape[0]
    m = X.shape[2] if data_batched else X.shape[1]
    d = X.shape[3] if data_batched else X.shape[2]
    for sub in set(subs):
        sub.validate(T, m, d)
    axes = _resolve_mesh(mesh, topology, m)

    losses = np.zeros((n, T), np.float32)
    errors = np.zeros((n, T), np.float32)
    round_bytes = np.zeros((n, T), np.int64)
    flags = np.zeros((n, T), bool)
    divs = np.zeros((n, T), np.float32)
    eps = np.zeros((n, T), np.float32)

    by_group: dict = {}
    for i, (s, p) in enumerate(zip(subs, pcfgs)):
        by_group.setdefault((s, p.kind), []).append(i)

    for (sub, kind), idx in sorted(
            by_group.items(),
            key=lambda kv: (PROTOCOL_KIND_CODES[kv[0][1]], repr(kv[0][0]))):
        fn = _jitted(sub, kind, bool(record_divergence), True, data_batched,
                     topology, mesh, axes)
        params = _stack_params([pcfgs[i] for i in idx])
        Xg = jnp.asarray(X[idx]) if data_batched else jnp.asarray(X)
        Yg = jnp.asarray(Y[idx]) if data_batched else jnp.asarray(Y)
        outs = fn(params, Xg, Yg)
        lo, er, nb, dv, fl, ep = (np.asarray(o) for o in outs)
        # (n, T, m) per-learner series -> (n, T), summed exactly as in run
        losses[idx], errors[idx], flags[idx] = _row_sums(lo), _row_sums(er), fl
        round_bytes[idx], divs[idx], eps[idx] = nb, dv, ep

    keep_div = record_divergence or all(s.free_divergence for s in subs)
    keep_eps = any(s.has_eps for s in subs)
    return SweepResult(
        configs=pcfgs,
        losses=losses,
        errors=errors,
        round_bytes=round_bytes,
        sync_flags=flags,
        divergences=divs if keep_div else None,
        eps=eps if keep_eps else None,
    )
