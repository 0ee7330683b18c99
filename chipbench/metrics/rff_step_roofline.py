"""``_rff_step_kernel``'s share of its roofline: the least time of its
launches (chipbench/counts.py at unpadded shapes, the larger of the
compute and the HBM bound) over their summed device time
(layer: kernels)."""

from chipbench import counts


def read(r):
    k = r.kernel("_rff_step_kernel")
    if k is None or r.peak is None:
        return None
    seconds, launches = k
    ops, nbytes = counts.rff_step(r.learners_per_chip, r.cfg["num_features"], r.cfg["dim"])
    least = max(ops / r.peak["flops_per_s"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * launches * least / seconds
