"""Device time per traced step of the operations under the program's
``adamw`` scope: the gradient's global-norm clip and the AdamW update."""

import named


def read(run: dict, peaks: dict):
    return named.scope_ms(run, "adamw")
