"""Benchmark harness entry point — one module per paper table.

Prints ``name,us_per_call,derived`` CSV lines and persists JSON artifacts
under ``artifacts/bench/``.

  throughput         — Table 1 / 13 / 14 (all methods × datasets × 2B/8B)
  ablations          — Tables 2 / 3 / 17 + App. P clamp
  protocol_audit     — Tables 4 / 5 + Corollary 1
  join_and_scaling   — Tables 18 / 21 + Fig. 2b / App. K
  roofline_bench     — §Roofline (reads dry-run artifacts)
  streaming          — eager vs streaming vs prefetch data paths
                       (emits BENCH_streaming.json; also `run.py --streaming`)
  layout             — measured dense vs packed batch layouts on real jitted
                       steps (emits BENCH_layout.json; also `run.py --layout`)
  kernels            — XLA blockwise vs Pallas flash fwd/bwd on packed rows +
                       live-tile census under segment-aware block skipping
                       (emits BENCH_kernels.json; also `run.py --kernels`)
  serving            — continuous vs static batching on the slot-cache serve
                       engine: tokens/s, p50/p99 latency, compile-once census
                       (emits BENCH_serving.json; also `run.py --serving`)
  faults             — deterministic chaos scenarios with bounded-termination
                       and bit-exact/accounted recovery rails
                       (emits BENCH_faults.json; also `run.py --faults`)
  multihost          — sharded-window host-count sweep with the §16
                       digest-equality + elastic-resume rails
                       (emits BENCH_multihost.json; also `run.py --multihost`)

Select one module by name (``run.py streaming``) or flag (``run.py
--streaming``); no argument runs everything.
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        ablations,
        faults,
        join_and_scaling,
        kernels,
        layout,
        multihost,
        protocol_audit,
        roofline_bench,
        serving,
        streaming,
        throughput,
    )

    modules = [
        ("throughput", throughput),
        ("ablations", ablations),
        ("protocol_audit", protocol_audit),
        ("join_and_scaling", join_and_scaling),
        ("roofline", roofline_bench),
        ("streaming", streaming),
        ("layout", layout),
        ("kernels", kernels),
        ("serving", serving),
        ("faults", faults),
        ("multihost", multihost),
    ]
    only = sys.argv[1].lstrip("-") if len(sys.argv) > 1 else None
    names = [name for name, _ in modules]
    if only is not None and only not in names:
        raise SystemExit(f"unknown benchmark module {only!r}; choose from {names}")
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        if only and only != name:
            continue
        t0 = time.perf_counter()
        try:
            for line in mod.main([]):
                print(line, flush=True)
            print(f"{name}/__wall__,{1e6*(time.perf_counter()-t0):.0f},ok=1", flush=True)
        except Exception as exc:  # pragma: no cover
            failures += 1
            print(f"{name}/__error__,0.0,error={type(exc).__name__}:{exc}", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
