"""Online learning algorithms A = (H, phi, ell) used by the protocols.

All learners expose a functional update

    update(state, (x, y)) -> (new_state, loss)

and are members of the (approximately) loss-proportional convex update
family the paper's analysis requires:

- drift bound:      ||f - phi(f, x, y)||  <=  eta * ell(f, x, y)
- convex target:    the update moves toward the minimizer set of ell
- gamma-proportional: ||phi(f) - phi(g)||^2 <= ||f-g||^2
                      - gamma^2 (ell(f) - ell(g))^2

Implemented:
- ``KernelSGD``  — NORMA (Kivinen, Smola, Williamson 2004): regularized
  SGD in an RKHS; coefficient decay (1 - eta*lam) plus one new SV per
  lossy round.  With a fixed budget the slot eviction is the truncation
  compression, making the update *approximately* loss-proportional
  (Lemma 3) with the epsilon of compression.py.
- ``KernelPA``   — kernel Passive-Aggressive (Crammer et al. 2006):
  exactly loss-proportional convex update, tau_pa = min(C, ell/k(x,x)).
- ``LinearSGD`` / ``LinearPA`` — the Euclidean originals from [10],
  used as the paper's linear baselines (Figs. 1 and 2).

Losses: ``hinge`` (classification, y in {-1,+1}) and ``squared``
(regression).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .rkhs import (
    KernelSpec,
    SVModel,
    empty_model,
    insert_sv,
    predict,
    rowsum,
    scale_model,
)

Array = jnp.ndarray

# A global cap on the number of learners used only to mint unique
# support-vector ids (id = counter * MAX_LEARNERS + learner_id).
MAX_LEARNERS = 4096

# sv_ids are minted in int32 (the dtype of rkhs.SVModel.sv_id and of the
# whole sorted-id set algebra behind the byte ledger: rkhs.sorted_unique
# pads with ID_SENTINEL = int32 max, accounting.DeviceLedger stores
# int32 arrays).  With id = counter * MAX_LEARNERS + learner_id the
# counter may not exceed this bound or the id wraps negative and the
# slot silently reads as *empty*, corrupting the Sec. 3 accounting.
# The counter increments at most once per processed example, so any
# driver can enforce the bound up front from its round count T via
# ``check_id_capacity`` (engine.run/sweep, the serial oracle, and the
# async harness all do).  Minting in int64 instead would need
# jax_enable_x64, which the launchers keep off — so the bound is
# guarded, not widened: ~524k insertions per learner.
MAX_INSERTIONS_PER_LEARNER = (2**31 - 1) // MAX_LEARNERS


def check_id_capacity(num_rounds: int) -> None:
    """Refuse runs long enough to wrap the int32 sv_id space.

    ``num_rounds`` is an upper bound on any learner's insertion counter
    (one insertion per lossy round).  Raises ValueError beyond
    ``MAX_INSERTIONS_PER_LEARNER``.
    """
    if num_rounds > MAX_INSERTIONS_PER_LEARNER:
        raise ValueError(
            f"{num_rounds} rounds can mint sv_ids past int32 "
            f"(counter * MAX_LEARNERS + learner_id wraps after "
            f"{MAX_INSERTIONS_PER_LEARNER} insertions per learner); "
            "shard the stream into shorter runs")


@dataclasses.dataclass(frozen=True)
class LearnerConfig:
    """Configuration of an online learner.

    algo: kernel_sgd | kernel_pa | linear_sgd | linear_pa
    loss: hinge | squared
    eta: learning rate (SGD); also the drift constant of Prop. 6.
    lam: regularization (NORMA decay (1 - eta*lam)).
    C: PA aggressiveness cap.
    budget: SV budget tau (kernel learners).
    evict: smallest | oldest  (inline truncation policy).
    kernel: KernelSpec for the RKHS.
    dim: input dimensionality d.
    """

    algo: str = "kernel_sgd"
    loss: str = "hinge"
    eta: float = 0.5
    lam: float = 0.01
    C: float = 1.0
    budget: int = 64
    evict: str = "smallest"
    kernel: KernelSpec = dataclasses.field(default_factory=KernelSpec)
    dim: int = 8

    def __post_init__(self):
        if self.algo not in ("kernel_sgd", "kernel_pa", "linear_sgd", "linear_pa"):
            raise ValueError(f"unknown algo {self.algo!r}")
        if self.loss not in ("hinge", "squared"):
            raise ValueError(f"unknown loss {self.loss!r}")

    @property
    def is_kernel(self) -> bool:
        return self.algo.startswith("kernel")


class KernelLearnerState(NamedTuple):
    model: SVModel
    counter: Array      # int32 — per-learner insertion counter
    learner_id: Array   # int32 — index of this learner in [m]


class LinearLearnerState(NamedTuple):
    w: Array            # (d,)
    b: Array            # ()


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def loss_and_grad(loss: str, yhat: Array, y: Array) -> Tuple[Array, Array]:
    """Returns (ell, dell/dyhat).  Shared by the learners here and the
    primal substrates (core/substrate.py)."""
    if loss == "hinge":
        ell = jnp.maximum(0.0, 1.0 - y * yhat)
        g = jnp.where(ell > 0.0, -y, 0.0)
        return ell, g
    # squared
    r = yhat - y
    return 0.5 * r * r, r


_loss_and_grad = loss_and_grad


# ---------------------------------------------------------------------------
# Kernel learners
# ---------------------------------------------------------------------------


def init_kernel_state(cfg: LearnerConfig, learner_id: int) -> KernelLearnerState:
    return KernelLearnerState(
        model=empty_model(cfg.budget, cfg.dim),
        counter=jnp.zeros((), jnp.int32),
        learner_id=jnp.asarray(learner_id, jnp.int32),
    )


def kernel_update(
    cfg: LearnerConfig, state: KernelLearnerState, example: Tuple[Array, Array]
) -> Tuple[KernelLearnerState, Array]:
    x, y = example
    yhat = predict(cfg.kernel, state.model, x[None])[0]
    return kernel_update_from_yhat(cfg, state, example, yhat)


def kernel_update_from_yhat(
    cfg: LearnerConfig,
    state: KernelLearnerState,
    example: Tuple[Array, Array],
    yhat: Array,
) -> Tuple[KernelLearnerState, Array]:
    """``kernel_update`` with the prediction supplied by the caller.

    The fused scan round (core/substrate.py) computes yhat once per
    round and feeds it both to the loss record and here, halving the
    Gram work per round; passing exactly ``predict(...)``'s value makes
    this bit-identical to ``kernel_update``.
    """
    x, y = example
    f = state.model
    ell, g = _loss_and_grad(cfg.loss, yhat, y)

    kxx = {
        "gaussian": jnp.asarray(1.0, jnp.float32),
        "linear": rowsum(x * x),
        "poly": (rowsum(x * x) + cfg.kernel.coef0) ** cfg.kernel.degree,
    }[cfg.kernel.kind]

    if cfg.algo == "kernel_sgd":
        f = scale_model(f, 1.0 - cfg.eta * cfg.lam)
        alpha_new = -cfg.eta * g
    else:  # kernel_pa
        tau_pa = jnp.minimum(cfg.C, ell / jnp.maximum(kxx, 1e-12))
        direction = y if cfg.loss == "hinge" else -jnp.sign(yhat - y)
        alpha_new = tau_pa * direction

    new_id = state.counter * MAX_LEARNERS + state.learner_id
    do_insert = jnp.abs(alpha_new) > 0.0

    f_ins = insert_sv(f, x, alpha_new, new_id, evict=cfg.evict)
    f2 = SVModel(
        sv=jnp.where(do_insert, f_ins.sv, f.sv),
        alpha=jnp.where(do_insert, f_ins.alpha, f.alpha),
        sv_id=jnp.where(do_insert, f_ins.sv_id, f.sv_id),
    )
    new_state = KernelLearnerState(
        model=f2,
        counter=state.counter + do_insert.astype(jnp.int32),
        learner_id=state.learner_id,
    )
    return new_state, ell


# ---------------------------------------------------------------------------
# Linear learners (the paper's baselines)
# ---------------------------------------------------------------------------


def init_linear_state(cfg: LearnerConfig) -> LinearLearnerState:
    return LinearLearnerState(w=jnp.zeros((cfg.dim,), jnp.float32), b=jnp.zeros((), jnp.float32))


def linear_update(
    cfg: LearnerConfig, state: LinearLearnerState, example: Tuple[Array, Array]
) -> Tuple[LinearLearnerState, Array]:
    x, y = example
    # multiply + fixed-order sum, not a dot: keeps the float result
    # independent of the learner-axis layout (rkhs.rowsum, DESIGN.md
    # Sec. 9)
    yhat = rowsum(state.w * x) + state.b
    ell, g = _loss_and_grad(cfg.loss, yhat, y)

    if cfg.algo == "linear_sgd":
        w = (1.0 - cfg.eta * cfg.lam) * state.w - cfg.eta * g * x
        b = state.b - cfg.eta * g
    else:  # linear_pa
        tau_pa = jnp.minimum(cfg.C, ell / jnp.maximum(rowsum(x * x) + 1.0, 1e-12))
        direction = y if cfg.loss == "hinge" else -jnp.sign(yhat - y)
        w = state.w + tau_pa * direction * x
        b = state.b + tau_pa * direction
    return LinearLearnerState(w=w, b=b), ell


# ---------------------------------------------------------------------------
# Uniform entry points
# ---------------------------------------------------------------------------


def init_state(cfg: LearnerConfig, learner_id: int = 0):
    if cfg.is_kernel:
        return init_kernel_state(cfg, learner_id)
    return init_linear_state(cfg)


def update(cfg: LearnerConfig, state, example):
    if cfg.is_kernel:
        return kernel_update(cfg, state, example)
    return linear_update(cfg, state, example)


def gamma_of(cfg: LearnerConfig) -> float:
    """The loss-proportionality constant used in Thm. 4's bound."""
    return cfg.eta if cfg.algo.endswith("sgd") else min(cfg.C, 1.0)
