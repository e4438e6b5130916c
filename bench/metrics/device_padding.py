"""Share of the device token slots of the window's steps that hold no real
token: 1 - real tokens / device tokens."""


def read(run: dict, peaks: dict):
    records = run["window"]["records"]
    device = sum(r[2] for r in records)
    return 100.0 * (1.0 - sum(r[0] for r in records) / device) if device else None
