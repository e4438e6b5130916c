"""Device time per traced step of the flash backward kernels: the dQ pass
(``flash_dq``) and the dK/dV pass (``flash_dkv``)."""

import named


def read(run: dict, peaks: dict):
    return named.kernel_ms(run, "flash_dq", "flash_dkv")
