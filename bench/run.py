"""ODB training benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the cell's TPU
chips.  The cells, metrics and bounds are in ``BENCHMARK.json``; a cell's
configuration, traffic and limits, its reference and operation counts, and
the metrics' readers are files under ``bench/`` found by name
(``bench/cell.py``).  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from the run's counters and a profiler trace of the window's last third.

Every run checks what its timed path produced against the plain reference
(``bench/check.py``), prints each number compared beside its limit as the
last lines of standard error, and prints one JSON object as the last line
of standard output.  Without a TPU, or outside a checkout of the repository,
it prints no result and exits 2.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

GIB = float(1 << 30)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def end_to_end(run: dict) -> dict:
    """The values of the end-to-end metrics, from the host clock."""
    w = run["window"]
    return {
        "tokens_per_s": sum(r[0] for r in w["records"]) / w["seconds"],
        "samples_per_s": sum(r[1] for r in w["records"]) / w["seconds"],
        "peak_hbm_gib": (run["memory_peak_bytes"] or 0) / GIB,
        "setup_s": run["setup_s"],
    }


def result_line(cell, run: dict, trace: bool) -> dict:
    import harness

    if trace:
        peaks = harness.peaks(run["device"]["kind"])
        metrics = {}
        for m in cell.per_layer:
            value = cell.module("metrics", m["name"]).read(run, peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    line = {
        "correct": run["correct"],
        "attempted": run["window"]["steps"],
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        tr = run["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in run["compared"].items()}
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no repro package under {src}; run from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import cell as cells
    import harness

    cell = cells.load_cell(ROOT, args.workload)
    try:
        run = harness.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                          t0=T0, root=ROOT)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    line = result_line(cell, run, bool(args.trace))
    for name, c in run["compared"].items():
        where = f" (leaf {c['leaf']})" if "leaf" in c else ""
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}{where}", file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
