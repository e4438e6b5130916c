"""Device time per traced step of the operations under the program's
``lm_loss`` scope, forward and backward: the final norm, the unembedding
and the cross-entropy."""

import named


def read(run: dict, peaks: dict):
    return named.scope_ms(run, "lm_loss")
