"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend (tests) the kernels execute under ``interpret=True``; on
TPU they always compile through Mosaic — nothing on this route falls back to
interpret mode when a chip is present.  ``flash_attention`` carries a
``custom_vjp`` whose backward runs the dedicated Pallas dq/dkv kernels
(:func:`~repro.kernels.flash_attention.segment_flash_attention_bwd`) from the
saved ``(q, k, v, out, lse)`` residuals — the recompute-free two-pass
formulation, so the training backward never round-trips through the O(S²)
jnp reference (``kernels/ref.py`` remains the allclose oracle for tests
only).

Grid routing (DESIGN.md §17): ``grid ∈ {dense, pruned, auto}`` picks between
the dense ``(b, h, nq, nk)`` grid and the scalar-prefetch pruned grid that
skips dead kv-tile DMAs through a compacted liveness index.  ``auto``
resolves to pruned exactly when segment ids are present and the backend is
TPU; an explicit ``pruned`` is honored anywhere segments exist (interpret
mode included — that is how CPU tests and benches exercise the path) and
degrades to dense without them, since there is nothing to build liveness
from.  Block sizes are resolved once here (``resolve_blocks``) and threaded
through the ``custom_vjp`` nondiff args, so the forward and both backward
passes provably consume the same ``(block_q, block_kv)`` pair —
``select_block`` is not idempotent on raw requests, and letting each pass
re-resolve independently is how fwd/bwd grids could silently drift for
ragged S.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (
    resolve_blocks,
    segment_flash_attention,
    segment_flash_attention_bwd,
    segment_flash_attention_bwd_pruned,
    segment_flash_attention_pruned,
)
from repro.kernels.ssd_scan import ssd_scan

GRID_MODES = ("dense", "pruned", "auto")


def _interpret() -> bool:
    """Pallas interpret mode exactly on the CPU backend."""
    return jax.default_backend() == "cpu"


def resolve_grid(grid: str | None, segment_ids) -> str:
    """Resolve an ``attn_grid`` request to a concrete grid variant."""
    if grid is None:
        grid = "auto"
    if grid not in GRID_MODES:
        raise ValueError(f"grid must be one of {GRID_MODES}, got {grid!r}")
    if segment_ids is None:
        return "dense"  # no segments -> no liveness table to prune from
    if grid == "auto":
        return "pruned" if jax.default_backend() == "tpu" else "dense"
    return grid


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segment_ids, causal, block_q, block_kv, grid):
    if grid == "pruned":
        return segment_flash_attention_pruned(
            q, k, v, segment_ids,
            causal=causal, block_q=block_q, block_kv=block_kv,
            interpret=_interpret(), expect_resolved=True,
        )
    return segment_flash_attention(
        q, k, v, segment_ids,
        causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(), expect_resolved=True,
    )


def _flash_fwd(q, k, v, segment_ids, causal, block_q, block_kv, grid):
    fwd = (
        segment_flash_attention_pruned
        if grid == "pruned"
        else segment_flash_attention
    )
    out, lse = fwd(
        q, k, v, segment_ids,
        causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(), return_residuals=True, expect_resolved=True,
    )
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd(causal, block_q, block_kv, grid, res, g):
    q, k, v, segment_ids, out, lse = res
    bwd = (
        segment_flash_attention_bwd_pruned
        if grid == "pruned"
        else segment_flash_attention_bwd
    )
    dq, dk, dv = bwd(
        q, k, v, segment_ids, out, lse, g,
        causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(), expect_resolved=True,
    )
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v, segment_ids=None, causal=True, block_q=128, block_kv=128,
    grid="auto",
):
    """Public flash-attention entry: resolves the block pair and grid variant
    once, then dispatches through the custom_vjp with both pinned as nondiff
    args (one resolution per shape for fwd *and* bwd)."""
    s = q.shape[1]
    block_q, block_kv = resolve_blocks(s, block_q, block_kv)
    mode = resolve_grid(grid, segment_ids)
    return _flash(q, k, v, segment_ids, causal, block_q, block_kv, mode)


def ssd_chunked_scan(x, dt, a, b_proj, c_proj, *, chunk: int = 256):
    """Kernel-backed SSD: y = SSD(x, dt, a, B, C) with zero initial state."""
    adt = a[None, None, :] * dt
    return ssd_scan(
        x, adt.astype(jnp.float32), dt.astype(jnp.float32), b_proj, c_proj,
        chunk=chunk, interpret=_interpret(),
    )
