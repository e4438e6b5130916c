"""Roofline share of the Pallas flash kernels (forward, dQ, dK/dV): the least
time their required work allows on the chip, over their summed device time.

The required work is the same whatever grid or block size implements it
(bench/flops: same-document causal pairs, the backward at twice the
forward's products, no recompute; q, k, v, o, dO, dq, dk, dv and the
log-sum-exp once each).  Each step's least time is the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth.  Nothing
to read where no flash kernel ran (the dense layout routes attention to XLA).
"""

import named


def read(run: dict, peaks: dict):
    return named.roofline(run, peaks, "flash")
