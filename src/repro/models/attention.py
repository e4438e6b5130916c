"""Attention: GQA/MQA/MHA + MLA (DeepSeek-V3), KV caches, segment masking.

Two train/prefill implementations behind one entry point (DESIGN.md §11):
the pure-jnp (XLA) blockwise path below, and the Pallas segment-aware flash
kernel in ``repro.kernels`` (fused forward + tiled two-pass backward, same
masking contract, validated against ``ref.py``).  ``use_flash_attention``
routes between them from ``ArchConfig.attn_impl`` — "auto" takes the kernel
exactly when the batch is packed and the backend compiles Pallas (TPU); the
decode/cache path and MLA always use XLA.

Memory design: scores are never materialized at (S_q × S_k).  Queries are
processed in blocks via ``lax.scan`` with the mask computed per block from
positions/segments (no (B, S, S) bias tensor), and the block body is
``jax.checkpoint``-ed so the backward pass recomputes per-block probs instead
of saving them — O(S·block) live attention memory instead of O(S²), the
pure-XLA analogue of flash attention's tiling.

Masking contract (shared with the Pallas kernel): attention is allowed iff
``segment_ids`` match (padding carries segment 0) AND (causal ⇒ key position
≤ query position).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, rms_norm

Params = dict[str, Any]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, n_kv, d_head)
    v: jax.Array  # (B, S_max, n_kv, d_head)


class MLACache(NamedTuple):
    ckv: jax.Array  # (B, S_max, kv_lora_rank) — compressed latent
    k_rope: jax.Array  # (B, S_max, qk_rope_dim) — shared rope key


# ------------------------------------------------------------------------------
# Parameter construction
# ------------------------------------------------------------------------------


def make_attention_params(key, cfg, dtype) -> Params:
    if cfg.attn_kind == "mla":
        return _make_mla_params(key, cfg, dtype)
    keys = jax.random.split(key, 4)
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p: Params = {
        "wq": dense_init(keys[0], d, h * dh, dtype),
        "wk": dense_init(keys[1], d, kv * dh, dtype),
        "wv": dense_init(keys[2], d, kv * dh, dtype),
        "wo": dense_init(keys[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype=dtype)
        p["k_norm"] = jnp.ones((dh,), dtype=dtype)
    return p


def _make_mla_params(key, cfg, dtype) -> Params:
    keys = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "w_dq": dense_init(keys[0], d, cfg.q_lora_rank, dtype),
        "q_norm": jnp.ones((cfg.q_lora_rank,), dtype=dtype),
        "w_uq": dense_init(keys[1], cfg.q_lora_rank, h * (nope + rope), dtype),
        "w_dkv": dense_init(keys[2], d, cfg.kv_lora_rank + rope, dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype=dtype),
        "w_uk": dense_init(keys[3], cfg.kv_lora_rank, h * nope, dtype),
        "w_uv": dense_init(keys[4], cfg.kv_lora_rank, h * vdim, dtype),
        "wo": dense_init(keys[5], h * vdim, d, dtype),
    }


# ------------------------------------------------------------------------------
# Block masking
# ------------------------------------------------------------------------------


def _block_mask(
    q_pos,  # (B, qb)
    k_pos,  # (B, Sk)
    q_seg,  # (B, qb) | None
    k_seg,  # (B, Sk) | None
    k_limit,  # scalar | None — keys at positions >= limit are invalid (cache)
    causal: bool,
):
    """(B, qb, Sk) boolean allow-mask computed per query block."""
    allowed = jnp.ones((q_pos.shape[0], q_pos.shape[1], k_pos.shape[1]), bool)
    if causal:
        allowed &= k_pos[:, None, :] <= q_pos[:, :, None]
    if q_seg is not None and k_seg is not None:
        allowed &= (q_seg[:, :, None] == k_seg[:, None, :]) & (
            k_seg[:, None, :] > 0
        )
    if k_limit is not None:
        allowed &= k_pos[:, None, :] < k_limit
    return allowed


def _pick_block(s: int, preferred: int = 256) -> int:
    for b in (preferred, 128, 64, 32, 16, 8, 4, 2, 1):
        if b <= s and s % b == 0:
            return b
    return 1


# ------------------------------------------------------------------------------
# Kernel routing (DESIGN.md §11): XLA blockwise vs Pallas flash
# ------------------------------------------------------------------------------


def use_flash_attention(cfg, segments, cache) -> bool:
    """Route this call through the Pallas segment-aware flash kernel?

    Structural gates first: only GQA-layout attention without a KV cache
    (train / full-sequence forward) matches the kernel contract.  Then the
    ``attn_impl`` policy: "flash" forces the kernel (interpret mode off-TPU —
    the tests' path), "xla" forces the blockwise-scan path, "auto" picks the
    kernel exactly when the batch is packed (explicit segments, where the
    kernel's segment-range block skipping pays) and the backend compiles
    Pallas (TPU).
    """
    if cache is not None:
        return False
    impl = getattr(cfg, "attn_impl", "xla")
    if impl == "flash":
        return True
    if impl == "auto":
        return segments is not None and jax.default_backend() == "tpu"
    return False


def resolve_flash_grid(cfg, segments) -> str:
    """Concrete grid variant for this call (DESIGN.md §17): the config's
    ``attn_grid`` policy resolved against segment presence and backend —
    shared by the kernel dispatch and the autotune cache key."""
    from repro.kernels.ops import resolve_grid

    return resolve_grid(getattr(cfg, "attn_grid", "auto"), segments)


def _flash_blocks(
    cfg, s: int, b: int, h: int, kv: int, dh: int, dtype, has_segments,
    grid: str = "dense",
):
    """Resolve the (block_q, block_kv) schedule for one shape cell."""
    from repro.kernels.autotune import autotune_blocks, heuristic_blocks
    from repro.kernels.flash_attention import select_block

    if cfg.attn_block_q or cfg.attn_block_kv:
        # Partial pins are honored: the unset side falls back to 128
        # rather than dropping the explicit one.
        return (
            select_block(s, cfg.attn_block_q or 128),
            select_block(s, cfg.attn_block_kv or 128),
        )
    if cfg.attn_autotune:
        return autotune_blocks(
            b, s, h, kv, dh,
            dtype=dtype, causal=cfg.causal, has_segments=has_segments,
            grid=grid,
        )
    return heuristic_blocks(s)


# ------------------------------------------------------------------------------
# Blockwise SDPA (GQA layout)
# ------------------------------------------------------------------------------


def _block_sdpa(
    q,  # (B, Sq, K, G, dh)
    k,  # (B, Sk, K, dh)
    v,  # (B, Sk, K, dh)
    q_pos,  # (B, Sq)
    k_pos,  # (B, Sk)
    q_seg,  # (B, Sq) | None
    k_seg,  # (B, Sk) | None
    k_limit,  # scalar | None
    causal: bool,
    scale: float,
    q_block: int = 256,
):
    b, sq, kh, g, dh = q.shape
    blk = _pick_block(sq, q_block)

    def block_body(qi, qpi, qsi):
        scores = (
            jnp.einsum("bqkgd,bskd->bkgqs", qi, k).astype(jnp.float32) * scale
        )
        allowed = _block_mask(qpi, k_pos, qsi, k_seg, k_limit, causal)
        scores = jnp.where(allowed[:, None, None, :, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)

    if blk == sq:
        return block_body(q, q_pos, q_seg)

    nb = sq // blk
    qb = q.reshape(b, nb, blk, kh, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(b, nb, blk).transpose(1, 0, 2)
    qsb = (
        q_seg.reshape(b, nb, blk).transpose(1, 0, 2) if q_seg is not None else None
    )

    def scan_body(_, xs):
        if qsb is None:
            qi, qpi = xs
            qsi = None
        else:
            qi, qpi, qsi = xs
        return None, block_body(qi, qpi, qsi)

    xs = (qb, qpb) if qsb is None else (qb, qpb, qsb)
    _, outs = jax.lax.scan(jax.checkpoint(scan_body), None, xs)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kh, g, dh)


# ------------------------------------------------------------------------------
# GQA forward (train / prefill / decode)
# ------------------------------------------------------------------------------


def _head_constraint(t, mesh, head_axis: int):
    """Annotate per-head tensors with (possibly uneven) `model` sharding so
    GSPMD keeps head-parallel layout through the reshape instead of falling
    back to 'involuntary full rematerialization' (replicate-then-reshard) —
    the yi/arctic 56-head fix (§Perf lever).  Uneven constraints are legal on
    intermediates (GSPMD pads)."""
    if mesh is None or "model" not in mesh.axis_names:
        return t
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import batch_dp_axes

    dp = batch_dp_axes(t.shape[0], mesh)
    spec = [dp] + [None] * (t.ndim - 1)
    spec[head_axis] = "model"
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*spec)))


def gqa_attention(
    params: Params,
    x: jax.Array,  # (B, S, d)
    cfg,
    positions: jax.Array,  # (B, S)
    segments: jax.Array | None = None,
    cache: KVCache | None = None,
    cache_index: jax.Array | None = None,  # scalar or (B,): tokens cached
    mesh=None,
    dest_slot: jax.Array | None = None,  # (B, S): packed→slot scatter map
) -> tuple[jax.Array, KVCache | None]:
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    g = h // kv
    q = (x @ params["wq"]).reshape(b, s, h, dh)
    k = (x @ params["wk"]).reshape(b, s, kv, dh)
    v = (x @ params["wv"]).reshape(b, s, kv, dh)
    if cfg.attn_head_constraint:
        q = _head_constraint(q, mesh, 2)
        k = _head_constraint(k, mesh, 2)
        v = _head_constraint(v, mesh, 2)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None and dest_slot is not None:
        # Slot-scatter prefill (serving, DESIGN.md §12): attention itself is
        # the cache-free packed-segment path — flash-eligible, identical
        # masking contract — while the roped K/V stream is scattered into
        # per-request cache rows at (dest_slot, within-segment position).
        # Padding positions carry an out-of-range dest row, so their writes
        # drop; within-segment rope positions are exactly the per-slot
        # absolute positions the decode path replays against.
        ck = cache.k.at[dest_slot, positions].set(
            k.astype(cache.k.dtype), mode="drop"
        )
        cv = cache.v.at[dest_slot, positions].set(
            v.astype(cache.v.dtype), mode="drop"
        )
        new_cache = KVCache(k=ck, v=cv)
        if use_flash_attention(cfg, segments, None):
            from repro.kernels.ops import flash_attention

            grid = resolve_flash_grid(cfg, segments)
            bq, bkv = _flash_blocks(
                cfg, s, b, h, kv, dh, q.dtype, segments is not None, grid
            )
            out = flash_attention(q, k, v, segments, cfg.causal, bq, bkv, grid)
        else:
            out = _block_sdpa(
                q.reshape(b, s, kv, g, dh), k, v, positions, positions,
                segments, segments, None, cfg.causal, 1.0 / (dh**0.5),
            )
        return out.reshape(b, s, h * dh) @ params["wo"], new_cache

    if use_flash_attention(cfg, segments, cache):
        # Pallas fused path: the kernel's row-absolute causal mask plus the
        # segment-id mask realizes the identical objective as the XLA
        # blockwise path's within-segment positions (cross-segment pairs die
        # on the segment compare either way), so the two routes are
        # numerically interchangeable (tests/test_kernels.py).
        from repro.kernels.ops import flash_attention

        grid = resolve_flash_grid(cfg, segments)
        bq, bkv = _flash_blocks(
            cfg, s, b, h, kv, dh, q.dtype, segments is not None, grid
        )
        out = flash_attention(q, k, v, segments, cfg.causal, bq, bkv, grid)
        return out.reshape(b, s, h * dh) @ params["wo"], None

    q = q.reshape(b, s, kv, g, dh)

    new_cache = None
    if cache is not None:
        assert cache_index is not None
        if jnp.ndim(cache_index) == 1:
            # Per-slot cache frontier (continuous-batching decode): row i
            # writes its new K/V at its own offset ``cache_index[i]`` and
            # reads keys strictly below its frontier — every slot sits at a
            # different depth inside one fixed-shape step.
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            cols = (
                cache_index.astype(jnp.int32)[:, None]
                + jnp.arange(s, dtype=jnp.int32)[None, :]
            )
            ck = cache.k.at[rows, cols].set(k.astype(cache.k.dtype), mode="drop")
            cv = cache.v.at[rows, cols].set(v.astype(cache.v.dtype), mode="drop")
            k_limit = (cache_index.astype(positions.dtype)[:, None] + s)[:, :, None]
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), cache_index, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), cache_index, axis=1
            )
            k_limit = cache_index + s
        new_cache = KVCache(k=ck, v=cv)
        s_max = ck.shape[1]
        k_pos = jnp.broadcast_to(
            jnp.arange(s_max, dtype=positions.dtype), (b, s_max)
        )
        out = _block_sdpa(
            q, ck.astype(q.dtype), cv.astype(q.dtype),
            positions, k_pos, None, None, k_limit, cfg.causal,
            1.0 / (dh**0.5),
        )
    else:
        out = _block_sdpa(
            q, k, v, positions, positions, segments, segments, None,
            cfg.causal, 1.0 / (dh**0.5),
        )
    out = out.reshape(b, s, h * dh)
    return out @ params["wo"], new_cache


# ------------------------------------------------------------------------------
# MLA forward
# ------------------------------------------------------------------------------


def _mla_block_sdpa(
    q_nope,  # (B, Sq, H, nope)
    q_rope,  # (B, Sq, H, rope)
    k_nope,  # (B, Sk, H, nope)
    k_rope,  # (B, Sk, rope)
    v,  # (B, Sk, H, vdim)
    q_pos, k_pos, q_seg, k_seg, k_limit, causal, scale, q_block=256,
):
    b, sq, h, _ = q_nope.shape

    def block_body(qn, qr, qpi, qsi):
        scores = jnp.einsum("bqhd,bshd->bhqs", qn, k_nope).astype(jnp.float32)
        scores += jnp.einsum("bqhd,bsd->bhqs", qr, k_rope).astype(jnp.float32)
        scores *= scale
        allowed = _block_mask(qpi, k_pos, qsi, k_seg, k_limit, causal)
        scores = jnp.where(allowed[:, None, :, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    blk = _pick_block(sq)
    if blk == sq:
        return block_body(q_nope, q_rope, q_pos, q_seg)
    nb = sq // blk
    qn = q_nope.reshape(b, nb, blk, h, -1).transpose(1, 0, 2, 3, 4)
    qr = q_rope.reshape(b, nb, blk, h, -1).transpose(1, 0, 2, 3, 4)
    qpb = q_pos.reshape(b, nb, blk).transpose(1, 0, 2)
    qsb = q_seg.reshape(b, nb, blk).transpose(1, 0, 2) if q_seg is not None else None

    def scan_body(_, xs):
        if qsb is None:
            a, r, p = xs
            sgm = None
        else:
            a, r, p, sgm = xs
        return None, block_body(a, r, p, sgm)

    xs = (qn, qr, qpb) if qsb is None else (qn, qr, qpb, qsb)
    _, outs = jax.lax.scan(jax.checkpoint(scan_body), None, xs)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, -1)


def mla_attention(
    params: Params,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    segments: jax.Array | None = None,
    cache: MLACache | None = None,
    cache_index: jax.Array | None = None,
) -> tuple[jax.Array, MLACache | None]:
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / ((nope + rope) ** 0.5)

    cq = rms_norm(x @ params["w_dq"], params["q_norm"])
    q = (cq @ params["w_uq"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = x @ params["w_dkv"]
    ckv = rms_norm(dkv[..., : cfg.kv_lora_rank], params["kv_norm"])
    k_rope = apply_rope(
        dkv[..., cfg.kv_lora_rank :][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]

    if cache is not None and s == 1:
        # Decode — weight-absorbed latent attention: attend in the compressed
        # space so per-step cost is O(S·(kv_lora+rope)) per head and the
        # cache stays (kv_lora + rope) per token (the MLA memory win).
        assert cache_index is not None
        cckv = jax.lax.dynamic_update_slice_in_dim(
            cache.ckv, ckv.astype(cache.ckv.dtype), cache_index, axis=1
        )
        ckr = jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, k_rope.astype(cache.k_rope.dtype), cache_index, axis=1
        )
        new_cache = MLACache(ckv=cckv, k_rope=ckr)
        s_max = cckv.shape[1]
        w_uk = params["w_uk"].reshape(cfg.kv_lora_rank, h, nope)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
        scores = jnp.einsum("bshr,btr->bhst", q_lat, cckv).astype(jnp.float32)
        scores += jnp.einsum("bshr,btr->bhst", q_rope, ckr).astype(jnp.float32)
        scores *= scale
        k_pos = jnp.broadcast_to(jnp.arange(s_max, dtype=positions.dtype), (b, s_max))
        allowed = (k_pos[:, None, :] <= positions[:, :, None]) & (
            k_pos[:, None, :] < (cache_index + s)
        )
        scores = jnp.where(allowed[:, None, :, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(cckv.dtype)
        out_lat = jnp.einsum("bhst,btr->bshr", probs, cckv)
        w_uv = params["w_uv"].reshape(cfg.kv_lora_rank, h, vdim)
        out = jnp.einsum("bshr,rhv->bshv", out_lat, w_uv)
        out = out.reshape(b, s, h * vdim)
        return out @ params["wo"], new_cache

    # Train / prefill — direct (non-absorbed) form with blockwise SDPA.
    k_nope = (ckv @ params["w_uk"]).reshape(b, s, h, nope)
    v = (ckv @ params["w_uv"]).reshape(b, s, h, vdim)
    new_cache = None
    if cache is not None:  # prefill fills the latent cache
        cckv = jax.lax.dynamic_update_slice_in_dim(
            cache.ckv, ckv.astype(cache.ckv.dtype), cache_index, axis=1
        )
        ckr = jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, k_rope.astype(cache.k_rope.dtype), cache_index, axis=1
        )
        new_cache = MLACache(ckv=cckv, k_rope=ckr)
    out = _mla_block_sdpa(
        q_nope, q_rope, k_nope, k_rope, v,
        positions, positions, segments, segments, None, cfg.causal, scale,
    )
    out = out.reshape(b, s, h * vdim)
    return out @ params["wo"], new_cache


def apply_attention(params, x, cfg, positions, segments=None, cache=None, cache_index=None, mesh=None, dest_slot=None):
    """The attention mixer; its operations carry the ``attention`` scope."""
    if cfg.attn_kind == "mla" and dest_slot is not None:
        raise NotImplementedError(
            "slot-scatter prefill needs the GQA cache layout; MLA serving "
            "stays on the per-request prefill path (DESIGN.md §12)"
        )
    with jax.named_scope("attention"):
        if cfg.attn_kind == "mla":
            return mla_attention(params, x, cfg, positions, segments, cache, cache_index)
        return gqa_attention(
            params, x, cfg, positions, segments, cache, cache_index,
            mesh=mesh, dest_slot=dest_slot,
        )


def init_kv_cache(cfg, batch: int, max_len: int, dtype) -> KVCache | MLACache:
    if cfg.attn_kind == "mla":
        return MLACache(
            ckv=jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype=dtype),
            k_rope=jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype=dtype),
        )
    return KVCache(
        k=jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype=dtype),
        v=jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype=dtype),
    )
