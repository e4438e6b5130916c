"""Model FLOP utilisation of the traced steps: the operations their real
tokens require (bench/flops; no recompute, no padding) over the traced
window's length times the chips' bf16 peak."""


def read(run: dict, peaks: dict):
    tr = run.get("trace")
    if not tr or not tr["steps"]:
        return None
    peak = peaks["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * tr["model_flops"] / (tr["window_s"] * peak)
