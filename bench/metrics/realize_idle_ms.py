"""Device idle time per traced step while the step loop waited on the data
path: idle gaps of 50 us or more whose midpoint lies in the program's
``train/realize`` span on the step-loop thread (the harness's ``bench/pull``
inside it included).  Nothing to read where the trace holds no such span."""

import named


def read(run: dict, peaks: dict):
    red = named.for_run(run)
    return named.per_step_ms(run, red["realize_idle_s"]) if red else None
