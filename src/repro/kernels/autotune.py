"""Measured (block_q, block_kv) schedules for the flash kernels (DESIGN.md §11).

The flash kernel's tile shape is a real throughput knob: the MXU wants
128-lane tiles, but the best (block_q, block_kv) pair per *shape cell*
(B, S, H, KV, D, dtype, causal, packed?) depends on VMEM pressure and the
live-tile census, so it is picked from a short measured probe rather than a
table.  Results are cached per process and persisted next to the other
bench/plan artifacts (``artifacts/autotune/attn_blocks.json``) so repeated
launches — and the dry-run's compile cells — reuse one schedule.

The probe runs at trace time (block sizes are static arguments to the
kernel), on synthetic inputs of the real shape, timing forward + backward
through the ``flash_attention`` custom-vjp.  When autotuning is off
(``ArchConfig.attn_autotune = False``, the default) the heuristic schedule
is used: the largest block ≤ 128 dividing S, the same rule
``select_block`` applies to ragged shapes.
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.flash_attention import select_block

DEFAULT_CACHE_PATH = pathlib.Path("artifacts") / "autotune" / "attn_blocks.json"

# One in-process schedule per cache file, so an explicit cache_path (tests,
# side experiments) never bleeds into — or is served from — the default pool.
_CACHES: dict[str, dict[str, tuple[int, int]]] = {}


def heuristic_blocks(s: int) -> tuple[int, int]:
    """Probe-free default: square blocks at the largest divisor ≤ 128."""
    b = select_block(s, 128)
    return b, b


def candidate_blocks(s: int) -> list[tuple[int, int]]:
    """Candidate (block_q, block_kv) pairs — exact divisors of S only,
    capped at 128 (the kernel's ``select_block`` cap: larger requests would
    silently alias the 128 schedule and pollute the persisted cache)."""
    divs = [d for d in (128, 64, 32) if d <= s and s % d == 0]
    if not divs:
        divs = [select_block(s, 128)]
    return sorted({(bq, bk) for bq in divs for bk in divs})


def shape_key(
    b: int, s: int, h: int, kv: int, d: int,
    *, dtype=jnp.float32, causal: bool = True, has_segments: bool = False,
    grid: str = "dense",
) -> str:
    # Keyed by grid variant (DESIGN.md §17): the pruned scalar-prefetch grid
    # has a different DMA/compute balance per tile shape, so a schedule
    # measured on one grid must never be served to the other.
    return (
        f"{jax.default_backend()}/b{b}s{s}h{h}kv{kv}d{d}"
        f"/{jnp.dtype(dtype).name}/causal{int(causal)}/seg{int(has_segments)}"
        f"/grid.{grid}"
    )


def _load_cache(path: pathlib.Path) -> dict[str, tuple[int, int]]:
    cache = _CACHES.get(str(path))
    if cache is not None:
        return cache
    cache = _CACHES.setdefault(str(path), {})
    try:
        stored = json.loads(path.read_text())
    except (OSError, ValueError):
        return cache
    for key, pair in stored.items():
        cache.setdefault(key, (int(pair[0]), int(pair[1])))
    return cache


def _persist_cache(path: pathlib.Path, cache: dict[str, tuple[int, int]]) -> None:
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(
            json.dumps({k: list(v) for k, v in sorted(cache.items())}, indent=1)
        )
        os.replace(tmp, path)
    except OSError:  # read-only checkout: keep the in-process cache only
        pass


def cached_schedule(
    cache_path: str | os.PathLike | None = None,
) -> dict[str, tuple[int, int]]:
    """Snapshot of one cache file's measured schedule (benchmarks artifact)."""
    path = pathlib.Path(cache_path) if cache_path is not None else DEFAULT_CACHE_PATH
    return dict(_load_cache(path))


def _probe_segments(b: int, s: int) -> jax.Array:
    """Synthetic packed rows: a few segments plus a padding tail, so the
    probe exercises the segment-masked (block-skipping) kernel variant."""
    seg = np.zeros((b, s), np.int32)
    cuts = [0, s // 3, (2 * s) // 3, s - s // 8]
    for i in range(b):
        for j in range(len(cuts) - 1):
            seg[i, cuts[j] : cuts[j + 1]] = j + 1
    return jnp.asarray(seg)


def autotune_blocks(
    b: int, s: int, h: int, kv: int, d: int,
    *,
    dtype=jnp.float32,
    causal: bool = True,
    has_segments: bool = False,
    include_bwd: bool = True,
    repeats: int = 2,
    probe_batch: int = 2,
    cache_path: str | os.PathLike | None = None,
    grid: str = "dense",
) -> tuple[int, int]:
    """Measured (block_q, block_kv) for one shape cell, cached on disk.

    The probe batch is capped (default 2 rows) — tile timing is row-
    independent, so the full train batch need not be materialized.
    """
    path = pathlib.Path(cache_path) if cache_path is not None else DEFAULT_CACHE_PATH
    cache = _load_cache(path)
    key = shape_key(
        b, s, h, kv, d, dtype=dtype, causal=causal,
        has_segments=has_segments, grid=grid,
    )
    if key in cache:
        obs.counter(
            "kernel_autotune_cache_hits_total",
            help="autotune shape cells served from cache",
        ).inc()
        return cache[key]
    obs.counter(
        "kernel_autotune_cache_misses_total",
        help="autotune shape cells that ran the measured probe",
    ).inc()
    probe_t0 = time.perf_counter()

    from repro.kernels.ops import flash_attention  # late: avoid import cycle

    pb = max(1, min(b, probe_batch))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (pb, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (pb, s, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (pb, s, kv, d)).astype(dtype)
    seg = _probe_segments(pb, s) if has_segments else None

    timings: dict[tuple[int, int], float] = {}
    for bq, bk in candidate_blocks(s):
        def fwd(q_, k_, v_):
            return flash_attention(q_, k_, v_, seg, causal, bq, bk, grid)

        if include_bwd:
            def run(q_, k_, v_):
                loss = lambda *a: jnp.sum(fwd(*a).astype(jnp.float32) ** 2)
                return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)
        else:
            run = fwd
        timed = jax.jit(run)
        # Every candidate is an exact, Mosaic-legal divisor tile, so a
        # compile or runtime error here is a kernel bug: it propagates
        # instead of silently shrinking the candidate set.
        jax.block_until_ready(timed(q, k, v))  # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(timed(q, k, v))
        timings[(bq, bk)] = (time.perf_counter() - t0) / repeats
    best = min(timings, key=timings.get)
    cache[key] = best
    _persist_cache(path, cache)
    obs.default_tracer().complete(
        "kernels/autotune", probe_t0, time.perf_counter() - probe_t0,
        cat="kernels", key=key, block_q=best[0], block_kv=best[1],
    )
    return best
