"""Block-liveness tables for the scalar-prefetch flash grid (DESIGN.md §17).

PR 3's dense grid predicates dead (q, kv) tiles out of the MXU with
``pl.when`` but the Pallas pipeline still DMAs every kv tile — on the
longtail-packed census only ~0.20 of tiles are live, so ~80% of kv HBM
bandwidth is fetched and discarded.  The scalar-prefetch grid fixes the
fetch: a cheap XLA-side pass over per-block segment-id ranges builds, per
(batch, q-block) row, a *compacted* index of live kv blocks plus a per-row
live count.  ``PrefetchScalarGridSpec`` hands that index to the kv
``BlockSpec`` index_map; live blocks are visited in ascending order (so the
online-softmax accumulation sequence is bit-identical to the dense grid's),
and for grid steps past the live count the index map repeats the last live
block — Pallas skips the re-DMA when consecutive index_map results agree, so
dead kv tiles are never fetched.  The causal predicate folds into the
liveness table so causally-dead tiles prune too.

The same tables drive both backward passes: the q-stationary dQ pass reuses
the row index verbatim, and the kv-stationary dK/dV pass uses the transposed
*column* index (per (batch, kv-block): which q blocks attend into this kv
tile).

Everything here is plain jnp (jit- and shard_map-friendly — tables for a
sharded batch are built inside the sharded region from the local segment
shard) plus one numpy census mirror for benchmarks/CI.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import _SEG_BIG, select_block


class LivenessTables(NamedTuple):
    """Compacted live-block indices for one (segment_ids, block_q, block_kv).

    ``kv_idx[b, qb, t]`` is the t-th live kv block of q-block ``qb`` (row
    index, ascending), clamped to the last live block for ``t >=
    kv_count[b, qb]``; ``q_idx[b, kb, t]`` / ``q_count[b, kb]`` are the
    transposed column tables for the kv-stationary backward.  Rows with no
    live blocks (all-padding packed rows) carry count 0 and index 0.
    """

    kv_idx: jax.Array  # (B, nq, nk) int32
    kv_count: jax.Array  # (B, nq) int32
    q_idx: jax.Array  # (B, nk, nq) int32
    q_count: jax.Array  # (B, nk) int32


def _range_bounds(segment_ids: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """Per-block (lo, hi) over positive segment ids; lo = _SEG_BIG when the
    block is all padding.  Valid because ids are nondecreasing over the real
    prefix of a packed row (layout contract, DESIGN.md §10)."""
    b, s = segment_ids.shape
    n = s // block
    blocks = segment_ids.reshape(b, n, block)
    lo = jnp.min(jnp.where(blocks > 0, blocks, _SEG_BIG), axis=-1)
    hi = jnp.max(blocks, axis=-1)
    return lo, hi


def block_liveness(
    segment_ids: jax.Array, block_q: int, block_kv: int, *, causal: bool = True
) -> jax.Array:
    """(B, nq, nk) bool — the kernel's ``_block_live`` rule, vectorized:
    segment ranges overlap (ids 0 excluded) AND (causal ⇒ the q block can
    reach the kv block)."""
    _, s = segment_ids.shape
    nq, nk = s // block_q, s // block_kv
    q_lo, q_hi = _range_bounds(segment_ids, block_q)
    k_lo, k_hi = _range_bounds(segment_ids, block_kv)
    live = (
        (q_hi[:, :, None] > 0)
        & (k_hi[:, None, :] > 0)
        & (q_hi[:, :, None] >= k_lo[:, None, :])
        & (k_hi[:, None, :] >= q_lo[:, :, None])
    )
    if causal:
        qb = jnp.arange(nq, dtype=jnp.int32)
        kb = jnp.arange(nk, dtype=jnp.int32)
        reach = (qb[:, None] * block_q + block_q - 1) >= kb[None, :] * block_kv
        live &= reach[None]
    return live


def compact_index(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Compact a (..., n) liveness mask into (idx, count).

    ``idx[..., t]`` lists the live positions in ascending order for
    ``t < count[...]`` and repeats the *last* live position beyond it (the
    clamp that makes the Pallas pipeline skip dead-tail DMAs).  Stable: keys
    live positions below dead ones, argsorts, then gathers through the
    clamped step index."""
    n = live.shape[-1]
    ar = jnp.arange(n, dtype=jnp.int32)
    key = jnp.where(live, ar, n + ar)
    order = jnp.argsort(key, axis=-1).astype(jnp.int32)
    count = jnp.sum(live, axis=-1).astype(jnp.int32)
    step = jnp.broadcast_to(ar, live.shape)
    clamped = jnp.minimum(step, jnp.maximum(count[..., None] - 1, 0))
    idx = jnp.take_along_axis(order, clamped, axis=-1)
    return idx, count


def build_liveness_tables(
    segment_ids: jax.Array,
    *,
    block_q: int,
    block_kv: int,
    causal: bool = True,
) -> LivenessTables:
    """Row + column tables for one packed batch.  ``block_q`` / ``block_kv``
    must already be resolved (``select_block`` applied) — asserted so the
    tables can never disagree with the kernel grid."""
    _, s = segment_ids.shape
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)
    live = block_liveness(segment_ids, block_q, block_kv, causal=causal)
    kv_idx, kv_count = compact_index(live)
    q_idx, q_count = compact_index(jnp.swapaxes(live, 1, 2))
    return LivenessTables(kv_idx, kv_count, q_idx, q_count)


# -----------------------------------------------------------------------------
# Host-side fetch census (benchmarks / CI rails)
# -----------------------------------------------------------------------------


def fetched_tile_counts(
    segment_ids,
    s: int,
    block_q: int,
    block_kv: int,
    *,
    causal: bool = True,
    heads: int = 1,
    kv_heads: int = 1,
    head_dim: int = 64,
    itemsize: int = 4,
) -> dict:
    """Exact kv-tile DMA census for the forward grid, dense vs pruned.

    Mirrors the Pallas pipeline rule precisely: walking the (b, h, nq, nk)
    grid in row-major order, a kv tile is (re)fetched whenever the kv
    index_map result differs from the previous grid step's.  The dense grid
    maps step ik → kv block ik (every step fetches a new tile); the pruned
    grid maps through the clamped row index, so the dead tail of each row
    repeats the last live block and fetches nothing.  Bytes count both the k
    and v tiles (``2 · block_kv · head_dim · itemsize`` per fetch).
    """
    import numpy as np

    seg = np.asarray(segment_ids)
    bsz = seg.shape[0]
    block_q = select_block(s, block_q)
    block_kv = select_block(s, block_kv)
    nq, nk = s // block_q, s // block_kv
    g = max(heads // kv_heads, 1)

    live = np.asarray(
        block_liveness(jnp.asarray(seg), block_q, block_kv, causal=causal)
    )
    counts = live.sum(axis=-1)  # (B, nq)

    dense_fetches = 0
    pruned_fetches = 0
    prev_dense = None
    prev_pruned = None
    for ib in range(bsz):
        for ih in range(heads):
            kvh = ih // g
            for iq in range(nq):
                row_live = np.flatnonzero(live[ib, iq])
                cnt = int(counts[ib, iq])
                last = int(row_live[-1]) if cnt else 0
                for ik in range(nk):
                    tile_d = (ib, kvh, ik)
                    if tile_d != prev_dense:
                        dense_fetches += 1
                    prev_dense = tile_d
                    kb = int(row_live[ik]) if ik < cnt else last
                    tile_p = (ib, kvh, kb)
                    if tile_p != prev_pruned:
                        pruned_fetches += 1
                    prev_pruned = tile_p

    steps = bsz * heads * nq * nk
    tile_bytes = 2 * block_kv * head_dim * itemsize  # k + v
    return {
        "grid": [bsz, heads, nq, nk],
        "block_q": block_q,
        "block_kv": block_kv,
        "grid_steps": steps,
        "live_tiles": int(counts.sum()),
        "dense_fetches": dense_fetches,
        "pruned_fetches": pruned_fetches,
        "dense_fetched_fraction": dense_fetches / steps if steps else 0.0,
        "pruned_fetched_fraction": pruned_fetches / steps if steps else 0.0,
        "kv_tile_bytes": tile_bytes,
        "dense_fetched_bytes": dense_fetches * tile_bytes,
        "pruned_fetched_bytes": pruned_fetches * tile_bytes,
    }
