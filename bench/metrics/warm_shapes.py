"""Distinct step shapes warmed in set-up (host enumeration of the epoch)."""


def read(run: dict, peaks: dict):
    return run["warm_shapes"]
