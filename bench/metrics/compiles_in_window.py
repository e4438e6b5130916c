"""Change in ``train_compile_events_total`` across the window: train-step
traces inside it.  Should read 0."""


def read(run: dict, peaks: dict):
    return run["window"]["compiles"]
