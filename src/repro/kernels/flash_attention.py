"""Segment-aware causal flash attention — Pallas TPU kernels (fwd + bwd).

TPU-native adaptation of the paper's packing story (DESIGN.md §2, §11): ODB's
packed groups need contamination-free attention; on GPU that is a varlen
CUDA kernel (flash_attn_varlen), on TPU the natural form is *segment-id
masking fused into a tiled attention kernel*.

Forward tiling: grid = (batch, q_heads, num_q_blocks, num_kv_blocks), the
last axis sequential (TPU "arbitrary" dimension semantics) carrying the
online-softmax accumulators (m, l, acc) in VMEM scratch.  BlockSpecs pull one
(block_q × d) query tile and one (block_kv × d) key/value tile into VMEM per
step; GQA is expressed in the k/v index_map (kv head = q head // group).

Kernel layout: Mosaic requires the last two dimensions of every block to be
(8, 128)-aligned or whole, so the kernels run heads-major — q/o/dO/dQ as
(B, H, S, D), k/v/dK/dV as (B, KV, S, D) — and the public (B, S, H, D)
entry points transpose at the boundary.  Segment ids enter twice, as a
(B, S, 1) column for the query side and a (B, 1, S) row for the key side,
so the in-tile allow-mask is a plain (block_q, 1) == (1, block_kv)
broadcast.  The per-row softmax statistics (lse, delta) are (B, H, S, 1)
inside and (B, S, H) at the public boundary.

Block skipping: causally dead (q, kv) block pairs are skipped via
``pl.when``, and — with packed rows — so are *segment-disjoint* pairs.
Segment ids within a packed row are nondecreasing over the real prefix (the
padding tail carries 0), so each block covers a contiguous id range
``[lo, hi]``; a (q, kv) pair is live only when the ranges overlap:
``q_hi >= k_lo and k_hi >= q_lo`` (ids 0 excluded).  Packing therefore turns
directly into proportionally fewer live tiles (measured by
benchmarks/kernels.py as the live-tile fraction).

Backward: the standard recompute-free two-pass formulation.  The forward
saves per-row ``lse = m + log(l)``; the backward recomputes probabilities as
``p = exp(s - lse)`` tile by tile (never materializing O(S²)), with

    delta = rowsum(dO ⊙ O)            (precomputed outside the kernels)
    dV   += Pᵀ · dO                   (kv-stationary pass)
    dS    = P ⊙ (dO·Vᵀ − delta)
    dK   += scale · dSᵀ · Q           (kv-stationary pass)
    dQ   += scale · dS · K            (q-stationary pass)

Two kernels: a q-stationary pass (grid (b, h, nq, nk), kv sequential)
accumulating dQ, and a kv-stationary pass (grid (b, kv, nk, g·nq), the
sequential axis walking every (group member, q block) pair of one kv tile)
whose VMEM scratch accumulates the GQA group-sum in place — dK/dV leave the
kernel at kv-head resolution, with no per-q-head HBM intermediates.  Both
share the masking contract — allowed iff segments match (0 = padding) and
(causal ⇒ k_pos ≤ q_pos) — and the same block skipping, and rows whose
softmax mass is empty (l == 0, all-padding rows) contribute exactly zero
gradient because ``p`` is built under the mask.

Block sizes need not divide S: ``select_block`` drops to the largest
divisor at or under the request (ragged sequence cells degrade gracefully
instead of asserting).  The model's default request comes from the row
length (``kernels.autotune.heuristic_blocks``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_SEG_BIG = 1 << 30  # "no positive segment in this block" sentinel

# Scalar-prefetch tables are staged whole into SMEM (1 MiB on v5e, shared
# with Mosaic's own scalars).  One pruned pallas_call prefetches at most this
# many table bytes; larger batches are split into row groups (one call each).
SMEM_TABLE_BUDGET = 512 * 1024


def select_block(s: int, requested: int) -> int:
    """Largest block ≤ ``requested`` that divides ``s``.

    Keeps the kernel grid exact for ragged sequence cells instead of
    asserting ``s % block == 0``.  Divisors that are multiples of 8 (the
    fp32 sublane) are preferred so the compiled TPU path keeps
    Mosaic-legal tile shapes: (768, 512) → 384, (200, 128) → 40 (not 100),
    (96, 128) → 96.  Shapes with no aligned divisor (e.g. prime S) fall
    back to the largest divisor of any width — interpret-mode territory.
    """
    b = min(requested, s)
    unaligned = 1
    for c in range(b, 0, -1):
        if s % c:
            continue
        if c % 8 == 0:
            return c
        if unaligned == 1:
            unaligned = c
    return unaligned


def resolve_blocks(s: int, block_q: int, block_kv: int) -> tuple[int, int]:
    """Resolve one ``(block_q, block_kv)`` pair for sequence length ``s``.

    ``select_block`` is a projection onto the divisors of ``s`` but is *not*
    idempotent on arbitrary requests (``select_block(120, 15) == 8``, not
    15), so independently re-resolving in the forward and backward could in
    principle drift if the two passes ever saw different raw requests.  The
    routing layer (kernels/ops.py) calls this once per shape and threads the
    resolved pair through the ``custom_vjp`` nondiff args; both passes then
    assert the pair is a fixed point (``expect_resolved=True``) instead of
    silently re-resolving.
    """
    return select_block(s, block_q), select_block(s, block_kv)


def _check_resolved(s: int, block_q: int, block_kv: int) -> None:
    assert (block_q, block_kv) == resolve_blocks(s, block_q, block_kv), (
        f"block pair ({block_q}, {block_kv}) is not resolved for S={s}: "
        f"routing must pin resolve_blocks() once and pass the fixed point"
    )


def _block_live(causal, qb, kb, block_q, block_kv, qseg_ref, kseg_ref):
    """Scalar liveness of one (q, kv) block pair: causal reach AND (for
    packed rows) overlapping per-block segment-id ranges."""
    live = qb * block_q + block_q - 1 >= kb * block_kv if causal else True
    if qseg_ref is not None:
        qseg = qseg_ref[...]
        kseg = kseg_ref[...]
        q_lo = jnp.min(jnp.where(qseg > 0, qseg, _SEG_BIG))
        k_lo = jnp.min(jnp.where(kseg > 0, kseg, _SEG_BIG))
        q_hi = jnp.max(qseg)
        k_hi = jnp.max(kseg)
        seg_live = (q_hi > 0) & (k_hi > 0) & (q_hi >= k_lo) & (k_hi >= q_lo)
        live = seg_live if live is True else live & seg_live
    return live


def _tile_mask(qb, kb, block_q, block_kv, causal, qseg_ref, kseg_ref):
    """(block_q, block_kv) boolean allow-mask — the shared contract."""
    allowed = jnp.ones((block_q, block_kv), dtype=jnp.bool_)
    if causal:
        q_pos = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        k_pos = kb * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        allowed &= k_pos <= q_pos
    if qseg_ref is not None:
        qseg = qseg_ref[...]  # (block_q, 1) column
        kseg = kseg_ref[...]  # (1, block_kv) row
        allowed &= (qseg == kseg) & (kseg > 0)
    return allowed


# -----------------------------------------------------------------------------
# Grid walks: where each grid step sits in the (q block, kv block) plane
# -----------------------------------------------------------------------------
#
# A walk maps the kernel's grid indices (plus the scalar-prefetch table refs
# of the pruned grid) to logical tile coordinates (ib, ih, iq, ik) — batch
# row, q head, q block, kv block.  The same walk drives every BlockSpec
# index_map and, inside the kernel, the tile position and liveness, so the
# dense and pruned grids share one spec builder and one set of bodies.


@dataclasses.dataclass(frozen=True)
class _Walk:
    """One grid walk: q-stationary (fwd, dQ) or kv-stationary (dK/dV),
    dense or pruned (scalar-prefetched liveness tables)."""

    q_stationary: bool
    pruned: bool
    nq: int
    nk: int
    group: int
    causal: bool
    block_q: int
    block_kv: int

    @property
    def steps(self) -> int:
        """Length of the sequential (last) grid axis."""
        return self.nk if self.q_stationary else self.group * self.nq

    def grid(self, b: int, h: int) -> tuple[int, int, int, int]:
        if self.q_stationary:
            return (b, h, self.nq, self.nk)
        return (b, h // self.group, self.nk, self.group * self.nq)

    def coords(self, i0, i1, i2, t, tables=()):
        """Grid indices -> logical (ib, ih, iq, ik)."""
        if self.q_stationary:
            ik = tables[0][i0, i2, t] if self.pruned else t
            return i0, i1, i2, ik
        ih = i1 * self.group + t // self.nq
        qt = t % self.nq
        iq = tables[0][i0, i2, qt] if self.pruned else qt
        return i0, ih, iq, i2

    def position(self, tables, qseg_ref, kseg_ref):
        """In-kernel (qb, kb, step, live) of the current grid step."""
        i0, i1, i2, t = (pl.program_id(a) for a in range(4))
        _, _, qb, kb = self.coords(i0, i1, i2, t, tables)
        if not self.pruned:
            live = _block_live(
                self.causal, qb, kb, self.block_q, self.block_kv,
                qseg_ref, kseg_ref,
            )
        elif self.q_stationary:
            live = t < tables[1][i0, i2]
        else:
            live = t % self.nq < tables[1][i0, i2]
        return qb, kb, t, live


def _spec(kind: str, walk: _Walk, d: int) -> pl.BlockSpec:
    """BlockSpec of one operand kind under ``walk``'s index map.

    Kinds: "q" (q, o, dO, dQ tiles), "kv" (k, v, dK, dV tiles), "row"
    (lse / delta statistics), "qseg" / "kseg" (segment-id column / row).
    """
    bq, bk, g = walk.block_q, walk.block_kv, walk.group
    shape = {
        "q": (None, None, bq, d),
        "kv": (None, None, bk, d),
        "row": (None, None, bq, 1),
        "qseg": (None, bq, 1),
        "kseg": (None, 1, bk),
    }[kind]

    def index(i0, i1, i2, t, *tables):
        ib, ih, iq, ik = walk.coords(i0, i1, i2, t, tables)
        if kind in ("q", "row"):
            return ib, ih, iq, 0
        if kind == "kv":
            return ib, ih // g, ik, 0
        if kind == "qseg":
            return ib, iq, 0
        return ib, 0, ik

    return pl.BlockSpec(shape, index)


def _table_bytes_per_row(walk: _Walk) -> int:
    """SMEM bytes of one batch row's (index, count) table pair.  The TPU
    compiler pads an SMEM array's minor dimension to 128 words (a (B, 32, 32)
    index table takes as much room as a (B, 32, 128) one); the dimension
    above it is rounded to 8 here for margin."""
    rows, cols = (walk.nq, walk.nk) if walk.q_stationary else (walk.nk, walk.nq)
    pad = lambda n, m: -(-n // m) * m
    return 4 * (pad(rows, 8) * pad(cols, 128) + pad(rows, 128))


def _row_groups(b: int, walk: _Walk) -> list[tuple[int, int]]:
    """Split ``b`` batch rows into near-equal groups whose prefetch tables
    fit ``SMEM_TABLE_BUDGET`` (one pallas_call each)."""
    if not walk.pruned:
        return [(0, b)]
    per_row = _table_bytes_per_row(walk)
    per_call = SMEM_TABLE_BUDGET // per_row
    if per_call < 1:
        raise ValueError(
            f"pruned flash grid: one row's liveness tables take {per_row} B "
            f"of SMEM (nq={walk.nq}, nk={walk.nk}), over the "
            f"{SMEM_TABLE_BUDGET} B budget; use attn_grid='dense' or larger "
            f"blocks for this sequence length"
        )
    n = -(-b // per_call)
    edges = [i * b // n for i in range(n + 1)]
    return list(zip(edges[:-1], edges[1:]))


def _launch(kernel, walk, *, name, h, d, in_kinds, args, out_kinds, out_shapes,
            scratch, tables, interpret):
    """Run one pallas_call named ``name`` over ``walk``; the pruned walk
    prefetches its tables and splits the batch into SMEM-bounded row groups.
    Every grid and row group of one pass carries the same name, which the
    profiler trace shows on the kernel's device events."""
    in_specs = [_spec(k, walk, d) for k in in_kinds]
    out_specs = [_spec(k, walk, d) for k in out_kinds]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )
    b = args[0].shape[0]
    results = []
    for r0, r1 in _row_groups(b, walk):
        shapes = [
            jax.ShapeDtypeStruct((r1 - r0,) + s.shape[1:], s.dtype)
            for s in out_shapes
        ]
        rows = [a[r0:r1] for a in args]
        grid = walk.grid(r1 - r0, h)
        if walk.pruned:
            call = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
                    out_specs=out_specs, scratch_shapes=scratch,
                ),
                out_shape=shapes, compiler_params=params, interpret=interpret,
                name=name,
            )
            results.append(call(*(t[r0:r1] for t in tables), *rows))
        else:
            call = pl.pallas_call(
                kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=shapes, scratch_shapes=scratch,
                compiler_params=params, interpret=interpret, name=name,
            )
            results.append(call(*rows))
    if len(results) == 1:
        return results[0]
    return [jnp.concatenate(parts, axis=0) for parts in zip(*results)]


def _prepare(q, k, segment_ids, block_q, block_kv, scale, expect_resolved):
    b, s, h, d = q.shape
    kv = k.shape[2]
    assert h % kv == 0, (h, kv)
    scale = scale if scale is not None else 1.0 / (d**0.5)
    if expect_resolved:
        _check_resolved(s, block_q, block_kv)
    block_q, block_kv = resolve_blocks(s, block_q, block_kv)
    seg_args = []
    if segment_ids is not None:
        seg_args = [segment_ids[:, :, None], segment_ids[:, None, :]]
    return b, s, h, d, h // kv, scale, block_q, block_kv, seg_args


def _heads_major(x):
    """(B, S, N, D) <-> (B, N, S, D); also (B, S, H) -> (B, H, S)."""
    return jnp.swapaxes(x, 1, 2)


def _tables_for(segment_ids, tables, block_q, block_kv, causal):
    if tables is None:
        from repro.kernels.liveness import build_liveness_tables

        tables = build_liveness_tables(
            segment_ids, block_q=block_q, block_kv=block_kv, causal=causal
        )
    return tables


# -----------------------------------------------------------------------------
# Forward
# -----------------------------------------------------------------------------


def _fwd_kernel(*refs, walk, has_seg, has_lse, scale):
    n_tables = 2 if walk.pruned else 0
    tables, refs = refs[:n_tables], refs[n_tables:]
    q_ref, k_ref, v_ref = refs[:3]
    qseg_ref, kseg_ref = refs[3:5] if has_seg else (None, None)
    o_ref = refs[5 if has_seg else 3]
    lse_ref = refs[-4] if has_lse else None
    m_scratch, l_scratch, acc_scratch = refs[-3:]
    block_q, block_kv = walk.block_q, walk.block_kv
    qb, kb, step, live = walk.position(tables, qseg_ref, kseg_ref)

    @pl.when(step == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch[...], NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch[...])
        acc_scratch[...] = jnp.zeros_like(acc_scratch[...])

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        allowed = _tile_mask(
            qb, kb, block_q, block_kv, walk.causal, qseg_ref, kseg_ref
        )
        scores = jnp.where(allowed, scores, NEG_INF)

        m_prev = m_scratch[:, 0]
        l_prev = l_scratch[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1))
        safe_m = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.where(allowed, jnp.exp(scores - safe_m[:, None]), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc_scratch[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        m_scratch[...] = jnp.broadcast_to(m_new[:, None], m_scratch.shape)
        l_scratch[...] = jnp.broadcast_to(l_new[:, None], l_scratch.shape)
        acc_scratch[...] = acc

    @pl.when(step == walk.steps - 1)
    def _finalize():
        l = l_scratch[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scratch[...] / denom[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            m = m_scratch[:, 0]
            lse = jnp.where(l > 0.0, m + jnp.log(denom), NEG_INF)
            lse_ref[...] = lse[:, None].astype(lse_ref.dtype)


def _forward(q, k, v, segment_ids, *, pruned, causal, scale, block_q,
             block_kv, interpret, return_residuals, expect_resolved, tables):
    b, s, h, d, g, scale, block_q, block_kv, seg_args = _prepare(
        q, k, segment_ids, block_q, block_kv, scale, expect_resolved
    )
    walk = _Walk(
        q_stationary=True, pruned=pruned, nq=s // block_q, nk=s // block_kv,
        group=g, causal=causal, block_q=block_q, block_kv=block_kv,
    )
    if pruned:
        t = _tables_for(segment_ids, tables, block_q, block_kv, causal)
        tables = (t.kv_idx, t.kv_count)
    has_seg = segment_ids is not None
    qh = _heads_major(q)
    out_kinds = ["q"]
    out_shapes = [jax.ShapeDtypeStruct(qh.shape, q.dtype)]
    if return_residuals:
        out_kinds.append("row")
        out_shapes.append(jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32))
    kernel = functools.partial(
        _fwd_kernel, walk=walk, has_seg=has_seg, has_lse=return_residuals,
        scale=scale,
    )
    outs = _launch(
        kernel, walk, name="flash_fwd", h=h, d=d,
        in_kinds=["q", "kv", "kv"] + (["qseg", "kseg"] if has_seg else []),
        args=[qh, _heads_major(k), _heads_major(v)] + seg_args,
        out_kinds=out_kinds, out_shapes=out_shapes,
        scratch=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        tables=tables, interpret=interpret,
    )
    out = _heads_major(outs[0])
    if return_residuals:
        return out, _heads_major(outs[1][..., 0])
    return out


def segment_flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,  # (B, S, KV, D)
    segment_ids: jax.Array | None = None,  # (B, S) int32; 0 = padding
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    return_residuals: bool = False,
    expect_resolved: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Forward kernel; with ``return_residuals`` also emits per-row
    ``lse = m + log(l)`` of shape (B, S, H) for the backward pass."""
    return _forward(
        q, k, v, segment_ids, pruned=False, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        return_residuals=return_residuals, expect_resolved=expect_resolved,
        tables=None,
    )


# -----------------------------------------------------------------------------
# Backward
# -----------------------------------------------------------------------------


def _recompute_p_ds(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
    *, scale, causal, block_q, block_kv, qb, kb,
):
    """Shared tile recompute: (p, ds) from the saved (lse, delta) residuals.

    ``p`` is assembled under the allow-mask, so fully-masked rows (the
    packed layout's l == 0 padding rows, whose saved lse is the NEG_INF
    sentinel) produce an all-zero tile rather than NaN/Inf.
    """
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    allowed = _tile_mask(qb, kb, block_q, block_kv, causal, qseg_ref, kseg_ref)
    lse = lse_ref[...].astype(jnp.float32)  # (block_q, 1)
    p = jnp.where(allowed, jnp.exp(scores - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    delta = delta_ref[...].astype(jnp.float32)  # (block_q, 1)
    ds = p * (dp - delta)
    return q, k, do, p, ds


def _split_bwd_refs(refs, walk, has_seg, n_out):
    n_tables = 2 if walk.pruned else 0
    tables, refs = refs[:n_tables], refs[n_tables:]
    ins = list(refs[:6])  # q, k, v, dO, lse, delta
    n_in = 8 if has_seg else 6
    seg = tuple(refs[6:8]) if has_seg else (None, None)
    outs = refs[n_in:n_in + n_out]
    scratch = refs[n_in + n_out:]
    return tables, ins, seg, outs, scratch


def _bwd_dq_kernel(*refs, walk, has_seg, scale):
    """q-stationary pass: dQ = scale · Σ_kv dS · K."""
    tables, ins, seg, (dq_ref,), (dq_scratch,) = _split_bwd_refs(
        refs, walk, has_seg, 1
    )
    qb, kb, step, live = walk.position(tables, *seg)

    @pl.when(step == 0)
    def _init():
        dq_scratch[...] = jnp.zeros_like(dq_scratch[...])

    @pl.when(live)
    def _compute():
        _, k, _, _, ds = _recompute_p_ds(
            *ins, *seg, scale=scale, causal=walk.causal,
            block_q=walk.block_q, block_kv=walk.block_kv, qb=qb, kb=kb,
        )
        dq_scratch[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ()))
        ) * scale

    @pl.when(step == walk.steps - 1)
    def _finalize():
        dq_ref[...] = dq_scratch[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, walk, has_seg, scale):
    """kv-stationary pass: dK = scale · Σ dSᵀ · Q, dV = Σ Pᵀ · dO.

    The sequential grid axis runs over (group member, q block) pairs —
    ``group · num_q_blocks`` steps per kv tile — so the GQA group-sum
    accumulates in the same VMEM scratch and the outputs land at kv-head
    resolution directly (no (B, S, H, D) per-q-head intermediates in HBM).
    The pruned walk maps the q step through the transposed column table, so
    each member only visits the q tiles that attend into this kv tile.
    """
    tables, ins, seg, (dk_ref, dv_ref), (dk_scratch, dv_scratch) = (
        _split_bwd_refs(refs, walk, has_seg, 2)
    )
    qb, kb, step, live = walk.position(tables, *seg)

    @pl.when(step == 0)
    def _init():
        dk_scratch[...] = jnp.zeros_like(dk_scratch[...])
        dv_scratch[...] = jnp.zeros_like(dv_scratch[...])

    @pl.when(live)
    def _compute():
        q, _, do, p, ds = _recompute_p_ds(
            *ins, *seg, scale=scale, causal=walk.causal,
            block_q=walk.block_q, block_kv=walk.block_kv, qb=qb, kb=kb,
        )
        dv_scratch[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dk_scratch[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ()))
        ) * scale

    @pl.when(step == walk.steps - 1)
    def _finalize():
        dk_ref[...] = dk_scratch[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scratch[...].astype(dv_ref.dtype)


def _backward(q, k, v, segment_ids, out, lse, do, *, pruned, causal, scale,
              block_q, block_kv, interpret, expect_resolved, tables):
    b, s, h, d, g, scale, block_q, block_kv, seg_args = _prepare(
        q, k, segment_ids, block_q, block_kv, scale, expect_resolved
    )
    nq, nk = s // block_q, s // block_kv
    if pruned:
        tables = _tables_for(segment_ids, tables, block_q, block_kv, causal)
    has_seg = segment_ids is not None

    # delta_i = Σ_d dO ⊙ O — one cheap rowwise pass outside the kernels.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qh, kh, vh = _heads_major(q), _heads_major(k), _heads_major(v)
    args = [
        qh, kh, vh, _heads_major(do),
        _heads_major(lse.astype(jnp.float32))[..., None],
        _heads_major(delta)[..., None],
    ] + seg_args
    in_kinds = ["q", "kv", "kv", "q", "row", "row"]
    in_kinds += ["qseg", "kseg"] if has_seg else []
    common = dict(
        nq=nq, nk=nk, group=g, causal=causal, block_q=block_q,
        block_kv=block_kv, pruned=pruned,
    )

    # -- pass 1: q-stationary dQ (pruned: the forward's row table) ----------
    walk = _Walk(q_stationary=True, **common)
    (dq,) = _launch(
        functools.partial(_bwd_dq_kernel, walk=walk, has_seg=has_seg, scale=scale),
        walk, name="flash_dq", h=h, d=d, in_kinds=in_kinds, args=args,
        out_kinds=["q"], out_shapes=[jax.ShapeDtypeStruct(qh.shape, q.dtype)],
        scratch=[pltpu.VMEM((block_q, d), jnp.float32)],
        tables=(tables.kv_idx, tables.kv_count) if pruned else None,
        interpret=interpret,
    )

    # -- pass 2: kv-stationary dK/dV (pruned: the transposed column table) --
    # Grid (b, kv_heads, nk, g·nq): the sequential axis walks every
    # (group member, q block) pair of one kv tile, so the GQA group-sum
    # accumulates in scratch and the outputs are kv-head resolution.
    walk = _Walk(q_stationary=False, **common)
    dk, dv = _launch(
        functools.partial(_bwd_dkv_kernel, walk=walk, has_seg=has_seg, scale=scale),
        walk, name="flash_dkv", h=h, d=d, in_kinds=in_kinds, args=args,
        out_kinds=["kv", "kv"],
        out_shapes=[
            jax.ShapeDtypeStruct(kh.shape, k.dtype),
            jax.ShapeDtypeStruct(vh.shape, v.dtype),
        ],
        scratch=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        tables=(tables.q_idx, tables.q_count) if pruned else None,
        interpret=interpret,
    )
    return _heads_major(dq), _heads_major(dk), _heads_major(dv)


def segment_flash_attention_bwd(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,  # (B, S, KV, D)
    segment_ids: jax.Array | None,
    out: jax.Array,  # (B, S, H, D) — forward output
    lse: jax.Array,  # (B, S, H) fp32 — forward log-sum-exp residual
    do: jax.Array,  # (B, S, H, D) — cotangent of out
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    expect_resolved: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Tiled two-pass backward: returns (dq, dk, dv) without ever
    materializing the (S × S) probability matrix."""
    return _backward(
        q, k, v, segment_ids, out, lse, do, pruned=False, causal=causal,
        scale=scale, block_q=block_q, block_kv=block_kv, interpret=interpret,
        expect_resolved=expect_resolved, tables=None,
    )


# -----------------------------------------------------------------------------
# Scalar-prefetch pruned grid (DESIGN.md §17)
# -----------------------------------------------------------------------------
#
# The dense grid above predicates dead tiles out of the MXU but still DMAs
# every kv tile.  The pruned variants keep the *static* grid shape (data-
# dependent grid sizes are impossible at trace time) and instead route the kv
# BlockSpec index_map through a compacted live-block index fed in via
# ``PrefetchScalarGridSpec``: step t of a row visits its t-th live kv block
# (ascending), and steps past the row's live count repeat the last live block
# — the Pallas pipeline skips the re-DMA when consecutive index_map results
# agree, so dead tiles are never fetched.  Compute is predicated on
# ``t < count``; init fires at t == 0 and finalize at the last grid step, so
# every output block is written even for rows with zero live tiles.
#
# Because live blocks are visited in the same ascending order the dense grid
# uses (which never touches the accumulators on dead tiles), the fp32
# accumulation sequence is identical and the pruned outputs/grads are
# bit-exact against the dense grid — asserted by tests and the bench parity
# rail, with the dense grid kept as the differential-testing oracle.
#
# The tables are prefetched whole into SMEM, so a batch whose tables exceed
# ``SMEM_TABLE_BUDGET`` runs as several row-group calls (``_row_groups``);
# a single row over budget is refused with a ValueError, never downgraded.


def segment_flash_attention_pruned(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,  # (B, S, KV, D)
    segment_ids: jax.Array,  # (B, S) int32; 0 = padding — required
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    return_residuals: bool = False,
    expect_resolved: bool = False,
    tables=None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Scalar-prefetch forward: dense-grid math, DMA-pruned kv fetch."""
    assert segment_ids is not None, "pruned grid requires segment ids"
    return _forward(
        q, k, v, segment_ids, pruned=True, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        return_residuals=return_residuals, expect_resolved=expect_resolved,
        tables=tables,
    )


def segment_flash_attention_bwd_pruned(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,  # required
    out: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
    expect_resolved: bool = False,
    tables=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pruned two-pass backward: the dQ pass reuses the forward row index,
    the dK/dV pass the transposed column index."""
    assert segment_ids is not None, "pruned grid requires segment ids"
    return _backward(
        q, k, v, segment_ids, out, lse, do, pruned=True, causal=causal,
        scale=scale, block_q=block_q, block_kv=block_kv, interpret=interpret,
        expect_resolved=expect_resolved, tables=tables,
    )


def live_tile_counts(
    segment_ids, s: int, block_q: int, block_kv: int, causal: bool = True
) -> dict:
    """Host-side mirror of the kernel's block-skip rule (benchmarks/tests).

    Counts (row, q-block, kv-block) tiles that survive (a) the causal skip
    alone and (b) causal + segment-range skipping, for a (B, S) segment-id
    array.  Pure numpy; mirrors ``_block_live`` exactly.
    """
    import numpy as np

    seg = np.asarray(segment_ids)
    bsz = seg.shape[0]
    block_q = select_block(s, block_q)
    block_kv = select_block(s, block_kv)
    nq, nk = s // block_q, s // block_kv
    total = bsz * nq * nk
    causal_live = 0
    seg_live = 0
    for i in range(bsz):
        for qb in range(nq):
            qs = seg[i, qb * block_q : (qb + 1) * block_q]
            q_pos = qs[qs > 0]
            for kb in range(nk):
                if causal and qb * block_q + block_q - 1 < kb * block_kv:
                    continue
                causal_live += 1
                ks = seg[i, kb * block_kv : (kb + 1) * block_kv]
                k_pos = ks[ks > 0]
                if (
                    q_pos.size
                    and k_pos.size
                    and q_pos.max() >= k_pos.min()
                    and k_pos.max() >= q_pos.min()
                ):
                    seg_live += 1
    return {
        "tiles": total,
        "block_q": block_q,
        "block_kv": block_kv,
        "causal_live": causal_live,
        "segment_live": seg_live,
        "causal_live_fraction": causal_live / total if total else 0.0,
        "segment_live_fraction": seg_live / total if total else 0.0,
    }
