"""The comparison that decides ``correct``.

The system's per-round outputs for one stream (summed loss, summed
errors, Sec. 3 bytes, sync flags, compression errors) are set beside
the plain reference's.  The protocol is a chain of threshold decisions
(a positive hinge loss inserts a support vector or moves the weights; a
local distance above delta syncs), so two correct float32 programs
part ways for good once a decision lands within rounding of its
threshold.  The reference reports how near each round's decisions
came; rounds are compared up to the first one whose margin is inside
the configuration's ``ambiguity`` band, and every decision before it
has to agree exactly.

The reference's per-learner losses are summed over the learners in
float32, row by row with numpy, as the engine's host code sums its own:
the rounding of that sum is then the same on both sides, and what is
left of a gap is how far the learners' own losses lie apart.
"""
from __future__ import annotations

import numpy as np

#: the numbers a comparison yields; a configuration's ``limits`` name
#: those its runs are held to
NAMES = ("loss_gap", "eps_gap", "sync_mismatch", "bytes_mismatch",
         "error_mismatch", "repeat_mismatch", "compared_rounds")


def summed(per_learner: np.ndarray) -> np.ndarray:
    """(T, m) float32 per-learner values summed over the learners in
    float32, in row-major order, as float64."""
    rows = np.ascontiguousarray(per_learner, dtype=np.float32)
    return rows.sum(axis=-1).astype(np.float64)


def compare(prog: dict, ref, ambiguity: dict) -> dict:
    """Numbers of one stream, over the rounds before the reference's
    first ambiguous decision (``compared_rounds`` of them): the mean gap
    of a round's summed loss, the mean relative gap of a sync's
    compression error, and the rounds whose syncs, bytes or error counts
    differ.  ``prog`` holds the system's per-round series: ``loss``,
    ``err``, ``bytes``, ``sync`` and ``eps`` (0 where no sync)."""
    amb = (ref.margin_amb < ambiguity["margin"]) | (ref.dist_amb < ambiguity["dist"])
    hits = np.nonzero(amb)[0]
    upto = int(hits[0]) if len(hits) else len(amb)
    sl = slice(0, upto)
    gap = np.abs(np.asarray(prog["loss"], np.float64)[sl] - summed(ref.loss)[sl])
    ref_err = summed(ref.err)
    sign_clear = ref.sign_amb[sl] >= ambiguity["margin"]
    both = np.asarray(prog["sync"])[sl] & ref.sync[sl]
    eps_p = np.asarray(prog["eps"], np.float64)[sl][both]
    eps_r = ref.eps.astype(np.float64)[sl][both]
    eps_gap = np.abs(eps_p - eps_r) / np.maximum(eps_r, 1e-6)
    return {
        "loss_gap": float(gap.mean()) if upto else 0.0,
        "eps_gap": float(eps_gap.mean()) if len(eps_gap) else 0.0,
        "sync_mismatch": int(np.sum(np.asarray(prog["sync"])[sl] != ref.sync[sl])),
        "bytes_mismatch": int(np.sum(np.asarray(prog["bytes"])[sl] != ref.nbytes[sl])),
        "error_mismatch": int(np.sum((np.asarray(prog["err"])[sl] != ref_err[sl])
                                     & sign_clear)),
        "compared_rounds": upto,
    }


def combine(per_stream: list) -> dict:
    """The numbers of several streams: the largest gap, the summed
    mismatch counts and compared rounds."""
    def worst(k):
        return sum if k.endswith("_mismatch") or k == "compared_rounds" else max
    return {k: worst(k)(p[k] for p in per_stream)
            for k in NAMES if k != "repeat_mismatch"}


def same(a: dict, b: dict) -> bool:
    """Two results of the system for the same stream are bitwise equal."""
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in ("loss", "err", "bytes", "sync", "eps"))


def value_of(name: str, numbers: dict):
    """The number a limit holds: a limit ``min_<number>`` is its least
    value, a limit of any other name its largest."""
    return numbers[name[len("min_"):] if name.startswith("min_") else name]


def within(numbers: dict, limits: dict) -> bool:
    """Every number the configuration sets a limit for is within it."""
    return all(value_of(k, numbers) >= v if k.startswith("min_")
               else value_of(k, numbers) <= v for k, v in limits.items())
