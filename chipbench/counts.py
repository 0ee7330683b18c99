"""Operations and HBM bytes of the kernels and of one protocol round,
counted from a cell's unpadded shapes.

An operation is one float32 add, multiply, compare or exponential.  A
Gaussian kernel value exp(-gamma max(|a|^2 + |b|^2 - 2 a.b, 0)) of two
d-vectors whose squared norms are known costs 2d (the cross term) + 6
(add, multiply by 2, subtract, max, multiply by gamma, exp); a squared
norm costs 2d.  Bytes are the least a launch must move: every input it
is given read once, every output written once, float32 and int32 at 4
bytes.
"""
from __future__ import annotations

F = 4                                   # bytes of a float32 / int32


def gauss_pair(d: int) -> int:
    return 2 * d + 6


def sv_predict(B: int, N: int, d: int):
    """(ops, bytes) of one ``sv_predict`` launch: B learners, each
    evaluating its N-slot expansion at one d-vector."""
    ops = B * (N * (gauss_pair(d) + 2) + N * 2 * d + 2 * d)
    nbytes = F * (B * d + B * N * d + B * N + B)
    return ops, nbytes


def quadform(B: int, M: int, N: int, d: int, shared: int):
    """(ops, bytes) of one batched ``quadform`` launch a^T K(X, Y) b over
    B learners with M and N slots.  ``shared`` is how many of the two
    (points, coefficients) operand pairs are the same for all B learners
    (0, 1 or 2): a shared pair is read once."""
    ops = B * (M * N * (gauss_pair(d) + 3) + (M + N) * 2 * d)
    pairs = [M, N]
    nbytes = F * B                                      # B outputs
    for i, n in enumerate(pairs):
        reads = 1 if i < shared else B
        nbytes += F * reads * n * (d + 1)
    return ops, nbytes


def rff_step(B: int, D: int, d: int):
    """(ops, bytes) of one fused ``rff_step`` launch: featurize (D x d
    projection, phase, cos, scale), predict (D multiply-adds plus the
    bias), hinge loss and gradient, and the decayed update of D + 1
    weights, for B learners."""
    ops = B * (D * (2 * d + 3) + 2 * D + 1 + 4 + 4 * D + 2)
    nbytes = F * (B * d + B + 2 * B * (D + 1) + D * d + D + 2 * B)
    return ops, nbytes


def sv_round_ops(cfg: dict, check: bool, syncs_per_round: float):
    """Operations of one protocol round of m SV learners with budget N:
    predict, the NORMA decay, the dynamic check when ``check`` (||f_i||^2
    and <f_i, r> per learner, ||r||^2 once), and the sync work (average,
    and the compression error over the m N averaged slots) times the
    share of rounds that sync."""
    m, N, d = cfg["learners"], cfg["budget"], cfg["dim"]
    ops = sv_predict(m, N, d)[0] + m * N
    if check:
        qf = lambda b: quadform(b, N, N, d, 0)[0]
        ops += 2 * qf(m) + qf(1) + 2 * m
    sync = m * N + quadform(1, m * N, m * N, d, 0)[0]
    return ops + syncs_per_round * sync


def rff_round_ops(cfg: dict, check: bool, syncs_per_round: float):
    """Operations of one protocol round of m RFF learners with D
    features: the fused step, the dynamic check (3 (D + 1) per learner)
    when ``check``, and the mean of the m models times the share of
    rounds that sync."""
    m, D, d = cfg["learners"], cfg["num_features"], cfg["dim"]
    ops = rff_step(m, D, d)[0]
    if check:
        ops += m * 3 * (D + 1)
    return ops + syncs_per_round * (m + 1) * (D + 1)
