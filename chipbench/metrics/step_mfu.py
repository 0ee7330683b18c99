"""The whole protocol round's share of the chips' peak FLOP/s: the
operations a round needs (chipbench/counts.py, at the cell's unpadded
shapes, with the syncs the traced experiments reported) times the
rounds completed per second of the traced window (layer: engine step)."""

from chipbench import counts


def read(r):
    ops_of = getattr(counts, r.cfg["kind"] + "_round_ops", None)
    if ops_of is None or r.peak is None:
        return None
    check = r.traffic["protocol"]["kind"] == "dynamic"
    ops = ops_of(r.cfg, check, r.syncs / r.rounds)
    return (100.0 * ops * r.rounds / r.window_s
            / (r.cfg["chips"] * r.peak["flops_per_s"]))
