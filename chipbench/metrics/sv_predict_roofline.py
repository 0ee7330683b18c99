"""``_sv_predict_kernel``'s share of its roofline: the least time its
launches could take on the chip (the larger of operations over peak
FLOP/s and bytes over peak HBM bandwidth, chipbench/counts.py at
unpadded shapes) over their summed device time (layer: kernels)."""

from chipbench import counts


def read(r):
    k = r.kernel("_sv_predict_kernel")
    if k is None or r.peak is None:
        return None
    seconds, launches = k
    ops, nbytes = counts.sv_predict(r.learners_per_chip, r.cfg["budget"], r.cfg["dim"])
    least = max(ops / r.peak["flops_per_s"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * launches * least / seconds
