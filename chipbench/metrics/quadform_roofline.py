"""``_quadform_kernel``'s share of its roofline.  A dynamic check
launches it three times: ||f_i||^2 batched over the chip's learners,
<f_i, r> with the reference shared, and ||r||^2 once.  The least time
of the three (chipbench/counts.py at unpadded shapes, the larger of the
compute and the HBM bound of each) over the launches' summed device
time (layer: kernels)."""

from chipbench import counts


def read(r):
    k = r.kernel("_quadform_kernel")
    if k is None or r.peak is None:
        return None
    seconds, launches = k
    B, N, d = r.learners_per_chip, r.cfg["budget"], r.cfg["dim"]
    least = 0.0
    for b, shared in ((B, 0), (B, 1), (1, 2)):
        ops, nbytes = counts.quadform(b, N, N, d, shared)
        least += max(ops / r.peak["flops_per_s"], nbytes / r.peak["hbm_bytes_per_s"])
    return 100.0 * (launches / 3) * least / seconds
