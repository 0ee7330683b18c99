"""The count functions against counts made by hand at small shapes."""
from chipbench import counts


def test_gauss_pair():
    # d=2: cross term 2 mul + 2 add, then add, x2, sub, max, x gamma, exp
    assert counts.gauss_pair(2) == 4 + 6


def test_sv_predict_small():
    # B=1 learner, N=2 slots, d=1: per slot a kernel value (2+6), times
    # alpha and accumulate (2), the slot's squared norm (2); the query's
    # squared norm once (2)
    ops, nbytes = counts.sv_predict(1, 2, 1)
    assert ops == 2 * (8 + 2) + 2 * 2 + 2
    # x (1), SV (2), A (2), out (1) floats
    assert nbytes == 4 * (1 + 2 + 2 + 1)


def test_quadform_small():
    # B=2, M=1, N=3, d=1: 3 pairs of (kernel 8 + a K b 3) per learner,
    # plus 4 squared norms of 2 per learner
    ops, nbytes = counts.quadform(2, 1, 3, 1, shared=0)
    assert ops == 2 * (3 * 11 + 4 * 2)
    # both operand pairs per learner: (1 + 3) x (point 1 + coefficient 1)
    assert nbytes == 4 * (2 + 2 * 1 * 2 + 2 * 3 * 2)
    _, shared1 = counts.quadform(2, 1, 3, 1, shared=1)
    assert shared1 == 4 * (2 + 1 * 2 + 2 * 3 * 2)
    _, shared2 = counts.quadform(2, 1, 3, 1, shared=2)
    assert shared2 == 4 * (2 + 1 * 2 + 3 * 2)


def test_rff_step_small():
    # B=1, D=2, d=1: per feature projection 2, phase 1, cos 1, scale 1
    # (2d+3=5), w.z 2, update 4; bias add 1, loss and gradient 4, b 2
    ops, nbytes = counts.rff_step(1, 2, 1)
    assert ops == 2 * 5 + 2 * 2 + 1 + 4 + 4 * 2 + 2
    # x 1, y 1, w and b in and out 2 x 3, W 2, phases 2, ell and yhat 2
    assert nbytes == 4 * (1 + 1 + 6 + 2 + 2 + 2)


def test_round_ops_add_up():
    cfg = {"learners": 2, "budget": 3, "dim": 1}
    base = counts.sv_round_ops(cfg, check=False, syncs_per_round=0.0)
    assert base == counts.sv_predict(2, 3, 1)[0] + 2 * 3
    checked = counts.sv_round_ops(cfg, check=True, syncs_per_round=0.0)
    qf = lambda b: counts.quadform(b, 3, 3, 1, 0)[0]
    assert checked - base == 2 * qf(2) + qf(1) + 2 * 2
    synced = counts.sv_round_ops(cfg, check=False, syncs_per_round=0.5)
    assert synced - base == 0.5 * (6 + counts.quadform(1, 6, 6, 1, 0)[0])
    rcfg = {"learners": 2, "num_features": 3, "dim": 1}
    r0 = counts.rff_round_ops(rcfg, check=False, syncs_per_round=0.0)
    assert r0 == counts.rff_step(2, 3, 1)[0]
    assert counts.rff_round_ops(rcfg, check=True, syncs_per_round=1.0) \
        == r0 + 2 * 3 * 4 + 3 * 4
