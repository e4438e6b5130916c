"""Readings that set the limits of `correct`, for one cell, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--config bench/configs/<other>.json]

For each seed: the program's first three steps, as a run's set-up drives
them, compared with the float32 reference (the lower readings).  For each
control seed also: the reference in bfloat16 put in the program's place
(the control), and the float32 reference with half of each batch left out
(a planted fault), each compared with the float32 reference (the upper
readings).  ``--config`` runs the program with another configuration file
of the same model, against the same reference of that file.  Not part of a
benchmark run: its output is the table in PERF.md that the limits come from.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import cell as cells  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def _numbers(compared: dict) -> dict:
    return {k: (v["value"], v.get("leaf")) for k, v in compared.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    cell = cells.load_cell(ROOT, args.workload)
    if args.config:
        cell = dataclasses.replace(cell, config=json.loads((ROOT / args.config).read_text()))
    harness.open_device(cell)
    trainer, _ = harness.build_trainer(cell)
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        loader = cells.make_loader(cell.traffic, trainer.model.cfg.vocab_size)
        trainer.loader = loader
        key = jax.random.PRNGKey(seed)
        state = jax.jit(trainer.init_state)(key)
        state, _, feed, program, probed = harness.first_steps(trainer, loader, key, state)
        feed.close()
        del state
        gc.collect()
        packed = loader.layout.needs_segments
        compared, ref = harness.verdict(cell, seed, program, probed, loader.last_audit, packed)
        row = {"seed": seed, "program": _numbers(compared)}
        if seed in args.control_seeds:
            batches = [{k: a[k] for k in ("tokens", "segments", "loss_mask")} for a, _ in probed]
            for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}), ("half_batch", {"drop_half": True})):
                other = reference.train3(cell.config, seed, batches, **kw)
                row[name] = _numbers(check.compare(other, ref, 0, 0.0, cell.limits))
        row["seconds"] = time.perf_counter() - t
        print("READING " + json.dumps(row), flush=True)
        rows.append(row)
    for name in ("program", "control_bf16", "half_batch"):
        got = [r[name] for r in rows if name in r]
        if got:
            summary = {k: [min(g[k][0] for g in got), max(g[k][0] for g in got)] for k in got[0]}
            print(f"SUMMARY {name} n={len(got)} " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
