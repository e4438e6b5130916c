"""Host time per window step that the step loop waited on the data path:
the change in ``train_realize_seconds_total`` over the window's steps."""


def read(run: dict, peaks: dict):
    steps = run["window"]["steps"]
    return 1000.0 * run["window"]["realize_s"] / steps if steps else None
