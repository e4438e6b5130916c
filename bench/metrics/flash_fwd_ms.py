"""Device time per traced step of the flash forward kernel (``flash_fwd``),
its recompute under rematerialisation included."""

import named


def read(run: dict, peaks: dict):
    return named.kernel_ms(run, "flash_fwd")
