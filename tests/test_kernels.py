"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode).

The flash-attention section also proves the *training* contract
(DESIGN.md §11): the custom-vjp backward runs the dedicated Pallas dq/dkv
kernels (never the jnp reference), and the kernel route through
``models/attention`` matches the XLA blockwise path — loss and gradients —
on packed batches with GQA, segments, and fully-masked padding rows (the
l == 0 denominator)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (
    live_tile_counts,
    resolve_blocks,
    segment_flash_attention,
    segment_flash_attention_bwd,
    select_block,
)
from repro.kernels.ops import flash_attention, ssd_chunked_scan
from repro.kernels.ref import segment_flash_attention_ref, ssd_scan_ref
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


def make_qkv(key, b, s, h, kv, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d)).astype(dtype)
    return q, k, v


def make_segments(key, b, s, max_segs=4):
    """Random packed layout with a padding tail."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 1 << 30)))
    seg = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = sorted(rng.choice(np.arange(8, s - 8), size=max_segs - 1, replace=False))
        bounds = [0] + list(cuts) + [s - rng.integers(0, s // 8)]
        for j in range(len(bounds) - 1):
            if bounds[j + 1] > bounds[j]:
                seg[i, bounds[j] : bounds[j + 1]] = j + 1
    return jnp.asarray(seg)


SHAPE_SWEEP = [
    # (B, S, H, KV, D, block_q, block_kv)
    (1, 128, 1, 1, 64, 64, 64),
    (2, 256, 4, 2, 64, 128, 64),
    (1, 512, 8, 8, 32, 128, 128),  # MHA
    (2, 256, 8, 1, 64, 64, 128),  # MQA
    (1, 384, 6, 2, 128, 128, 128),  # non-pow2 length multiple
]


class TestFlashAttention:
    @pytest.mark.parametrize("shape", SHAPE_SWEEP)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_ref(self, shape, dtype, causal):
        b, s, h, kv, d, bq, bk = shape
        q, k, v = make_qkv(jax.random.PRNGKey(0), b, s, h, kv, d, dtype)
        out = segment_flash_attention(
            q, k, v, None, causal=causal, block_q=bq, block_kv=bk, interpret=True
        )
        ref = segment_flash_attention_ref(q, k, v, None, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
        )

    @pytest.mark.parametrize("shape", SHAPE_SWEEP[:3])
    @pytest.mark.parametrize("causal", [True, False])
    def test_segments_vs_ref(self, shape, causal):
        b, s, h, kv, d, bq, bk = shape
        q, k, v = make_qkv(jax.random.PRNGKey(1), b, s, h, kv, d, jnp.float32)
        seg = make_segments(jax.random.PRNGKey(2), b, s)
        out = segment_flash_attention(
            q, k, v, seg, causal=causal, block_q=bq, block_kv=bk, interpret=True
        )
        ref = segment_flash_attention_ref(q, k, v, seg, causal=causal)
        valid = np.asarray(seg > 0)[:, :, None, None]
        np.testing.assert_allclose(
            np.where(valid, np.asarray(out), 0.0),
            np.where(valid, np.asarray(ref), 0.0),
            atol=3e-5, rtol=3e-5,
        )

    def test_no_cross_segment_contamination(self):
        """Changing tokens of segment 2 must not change segment 1 outputs."""
        b, s, h, kv, d = 1, 128, 2, 2, 32
        q, k, v = make_qkv(jax.random.PRNGKey(3), b, s, h, kv, d, jnp.float32)
        seg = jnp.asarray(np.repeat([[1] * 64 + [2] * 64], b, axis=0), jnp.int32)
        out1 = segment_flash_attention(q, k, v, seg, interpret=True, block_q=64, block_kv=64)
        k2 = k.at[:, 64:].set(jax.random.normal(jax.random.PRNGKey(9), (b, 64, kv, d)))
        v2 = v.at[:, 64:].set(jax.random.normal(jax.random.PRNGKey(10), (b, 64, kv, d)))
        out2 = segment_flash_attention(q, k2, v2, seg, interpret=True, block_q=64, block_kv=64)
        np.testing.assert_allclose(
            np.asarray(out1[:, :64]), np.asarray(out2[:, :64]), atol=1e-6
        )

    def test_custom_vjp_grads(self):
        b, s, h, kv, d = 1, 128, 2, 1, 32
        q, k, v = make_qkv(jax.random.PRNGKey(4), b, s, h, kv, d, jnp.float32)

        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(segment_flash_attention_ref(q, k, v) ** 2)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4)


# Document bounds as fractions of the row: three documents and a padding
# tail, or nine short ones, so that a 256-wide tile holds several.
FEW_DOCS = (0.0, 0.3, 0.55, 0.9)
MANY_DOCS = (0.0, 0.04, 0.13, 0.15, 0.3, 0.38, 0.55, 0.6, 0.74, 0.9)


def packed_test_segments(b: int, s: int, docs=FEW_DOCS):
    """Packed rows exercising every backward edge: multiple segments per
    row, a padding tail, and one fully-masked row (l == 0 everywhere)."""
    seg = np.zeros((b, s), np.int32)
    bounds = [int(s * f) for f in docs]
    for i in range(b - 1):
        for j in range(len(bounds) - 1):
            seg[i, bounds[j] : bounds[j + 1]] = j + 1
    # last row stays all-zero: an IDLE / all-padding row
    return jnp.asarray(seg)


class TestFlashBackward:
    """Pallas dq/dkv kernels vs the jnp oracle — the training contract."""

    def _masked_losses(self, seg):
        valid = (np.asarray(seg) > 0)[:, :, None, None].astype(np.float32)
        vm = jnp.asarray(valid)

        def loss_flash(q, k, v, *, bq=64, bk=64):
            out = flash_attention(q, k, v, seg, True, bq, bk)
            return jnp.sum((out.astype(jnp.float32) * vm) ** 2)

        def loss_ref(q, k, v):
            out = segment_flash_attention_ref(q, k, v, seg)
            return jnp.sum((out.astype(jnp.float32) * vm) ** 2)

        return loss_flash, loss_ref

    @pytest.mark.parametrize("shape", [
        (2, 256, 4, 2, 32), (2, 128, 8, 1, 64),
        # tiles above 128, several documents per tile
        (2, 1024, 4, 2, 32, (256, 256)), (2, 1024, 4, 2, 32, (256, 512)),
        # the rule's pairs
        (2, 1024, 4, 2, 32, (512, 512)), (2, 2048, 4, 2, 32, (512, 1024)),
    ])
    def test_segment_grads_vs_ref(self, shape):
        """GQA + segments + an all-padding row (l == 0 denominator)."""
        b, s, h, kv, d, *blocks = shape
        bq, bk = blocks[0] if blocks else (64, 64)
        q, k, v = make_qkv(jax.random.PRNGKey(7), b, s, h, kv, d, jnp.float32)
        seg = packed_test_segments(b, s, MANY_DOCS if blocks else FEW_DOCS)
        loss_flash, loss_ref = self._masked_losses(seg)
        loss_flash = functools.partial(loss_flash, bq=bq, bk=bk)
        g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, gr):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4
            )

    def test_bwd_never_recomputes_through_jnp_reference(self, monkeypatch):
        """The training backward must run the Pallas kernels, not ref.py."""
        from repro.kernels import ref as ref_mod

        def boom(*a, **kw):  # pragma: no cover - failure path
            raise AssertionError("jnp reference called inside the backward")

        monkeypatch.setattr(ref_mod, "segment_flash_attention_ref", boom)
        b, s, h, kv, d = 1, 128, 2, 1, 32
        q, k, v = make_qkv(jax.random.PRNGKey(8), b, s, h, kv, d, jnp.float32)
        grads = jax.grad(
            lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)

    def test_bwd_entry_point_direct(self):
        """segment_flash_attention_bwd == vjp of the oracle (fp32, mixed
        block shapes for the two passes)."""
        b, s, h, kv, d = 1, 256, 4, 4, 32
        q, k, v = make_qkv(jax.random.PRNGKey(9), b, s, h, kv, d, jnp.float32)
        out, lse = segment_flash_attention(
            q, k, v, None, interpret=True, return_residuals=True
        )
        g = jax.random.normal(jax.random.PRNGKey(10), out.shape)
        dq, dk, dv = segment_flash_attention_bwd(
            q, k, v, None, out, lse, g,
            block_q=128, block_kv=64, interpret=True,
        )
        _, vjp = jax.vjp(lambda *a: segment_flash_attention_ref(*a), q, k, v)
        rq, rk, rv = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=1e-4, rtol=1e-4)

    def test_ragged_sequence_blocks(self):
        """Satellite: no s % block assert — ragged S drops to the largest
        dividing block and still matches the oracle fwd + bwd."""
        assert select_block(384, 128) == 128
        assert select_block(200, 128) == 40  # sublane-aligned beats 100
        assert select_block(96, 128) == 96
        assert select_block(101, 128) == 101  # prime: any divisor fallback
        b, s, h, kv, d = 1, 200, 2, 2, 32
        q, k, v = make_qkv(jax.random.PRNGKey(11), b, s, h, kv, d, jnp.float32)
        out = segment_flash_attention(q, k, v, None, interpret=True)
        ref = segment_flash_attention_ref(q, k, v, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
        g = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda *a: jnp.sum(segment_flash_attention_ref(*a) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4)

    def test_block_skip_is_lossless(self):
        """Rows built so whole (q, kv) tile pairs are segment-disjoint: the
        skip must change the tile census, not the numbers."""
        b, s, h, kv, d = 1, 256, 2, 2, 32
        q, k, v = make_qkv(jax.random.PRNGKey(12), b, s, h, kv, d, jnp.float32)
        # segment ids aligned to 64-blocks: blocks 0..3 hold segs 1,2,3,pad
        seg = np.zeros((b, s), np.int32)
        seg[:, 0:64] = 1
        seg[:, 64:128] = 2
        seg[:, 128:192] = 3
        segj = jnp.asarray(seg)
        census = live_tile_counts(seg, s, 64, 64)
        assert census["segment_live"] < census["causal_live"]
        out = segment_flash_attention(q, k, v, segj, interpret=True, block_q=64, block_kv=64)
        ref = segment_flash_attention_ref(q, k, v, segj)
        valid = (seg > 0)[:, :, None, None]
        np.testing.assert_allclose(
            np.where(valid, np.asarray(out), 0.0),
            np.where(valid, np.asarray(ref), 0.0),
            atol=3e-5, rtol=3e-5,
        )


class TestKernelRouting:
    """models/attention routing: flash vs XLA blockwise parity end to end."""

    def _packed_batch(self, vocab=512, b=2, s=256):
        from repro.models.model import shift_labels

        rng = np.random.default_rng(0)
        tokens = np.zeros((b, s), np.int32)
        seg = np.zeros((b, s), np.int32)
        pos = np.zeros((b, s), np.int32)
        mask = np.zeros((b, s), np.float32)
        bounds = [(0, 100), (100, 230)]  # two packed samples + pad tail
        for sid, (a, e) in enumerate(bounds, start=1):
            tokens[0, a:e] = rng.integers(1, vocab, e - a)
            seg[0, a:e] = sid
            pos[0, a:e] = np.arange(e - a)
            mask[0, a:e] = 1.0
        # row 1 stays fully padding (IDLE row: the l == 0 path in training)
        batch = {
            "tokens": jnp.asarray(tokens),
            "positions": jnp.asarray(pos),
            "segments": jnp.asarray(seg),
        }
        labels, m = shift_labels(
            batch["tokens"], jnp.asarray(mask), segments=batch["segments"]
        )
        batch["labels"], batch["loss_mask"] = labels, m
        return batch

    def test_lm_loss_and_grads_match_xla_path(self):
        """Acceptance: Pallas-path loss AND gradients == XLA blockwise path
        on packed aligned groups (interpret mode on CPU)."""
        from repro.configs import get_smoke_config
        from repro.models import LM

        cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=512)
        batch = self._packed_batch()
        results = {}
        for impl in ("xla", "flash"):
            model = LM(dataclasses.replace(cfg, attn_impl=impl))
            params = model.init(jax.random.PRNGKey(0))

            def loss(p):
                ls, t = model.loss_sums(p, batch)
                return ls / jnp.maximum(t, 1.0)

            results[impl] = jax.value_and_grad(loss)(params)
        loss_x, grads_x = results["xla"]
        loss_f, grads_f = results["flash"]
        np.testing.assert_allclose(float(loss_x), float(loss_f), rtol=1e-6)
        for gx, gf in zip(
            jax.tree_util.tree_leaves(grads_x), jax.tree_util.tree_leaves(grads_f)
        ):
            np.testing.assert_allclose(
                np.asarray(gx, np.float32), np.asarray(gf, np.float32),
                atol=5e-6, rtol=5e-4,
            )

    def test_resolve_attn_impl_matrix(self):
        from repro.configs import get_smoke_config
        from repro.train.trainer import resolve_attn_impl

        cfg = get_smoke_config("qwen3_0_6b")
        assert cfg.attn_impl == "auto"
        # auto: flash only for packed layouts on a Pallas-compiling backend
        assert resolve_attn_impl(cfg, packed=True, backend="tpu") == "flash"
        assert resolve_attn_impl(cfg, packed=False, backend="tpu") == "xla"
        assert resolve_attn_impl(cfg, packed=True, backend="cpu") == "xla"
        # explicit pins win regardless of layout/backend
        pinned = dataclasses.replace(cfg, attn_impl="flash")
        assert resolve_attn_impl(pinned, packed=False, backend="cpu") == "flash"
        # MLA never routes to the kernel
        mla = get_smoke_config("deepseek_v3_671b")
        assert mla.attn_kind == "mla"
        assert resolve_attn_impl(mla, packed=True, backend="tpu") == "xla"

    def test_flash_pin_rejected_for_mla(self):
        from repro.configs import get_smoke_config
        from repro.models import LM

        mla = dataclasses.replace(
            get_smoke_config("deepseek_v3_671b"), attn_impl="flash"
        )
        with pytest.raises(ValueError, match="flash"):
            LM(mla)

    @pytest.mark.parametrize("s, blocks", [
        # every row length of the benchmark cells' windows
        (512, (512, 512)), (768, (384, 384)), (1024, (512, 512)),
        (1536, (512, 512)), (2048, (512, 512)), (3072, (512, 512)),
        (4096, (512, 1024)), (6144, (512, 1024)), (8192, (512, 1024)),
        # rows of other l_max grids, where 512 and 1024 do not divide S
        (640, (320, 128)), (1280, (320, 256)), (1792, (448, 256)),
        (1920, (480, 384)), (2176, (272, 128)), (7680, (512, 768)),
        # ragged and short rows
        (200, (200, 200)), (128, (128, 128)), (4104, (456, 72)),
    ])
    def test_heuristic_blocks_rule(self, s, blocks):
        from repro.kernels.autotune import MAX_TILE_AREA, heuristic_blocks

        bq, bk = heuristic_blocks(s)
        assert (bq, bk) == blocks
        assert resolve_blocks(s, bq, bk) == blocks
        assert bq * bk <= MAX_TILE_AREA
        # Mosaic's (8, 128) rule, wherever S has a divisor that keeps it
        assert bq % 8 == 0 or bq == s
        assert bk % 128 == 0 or bk == s or s % 128

    def test_flash_blocks_pins_and_rule(self):
        """Explicit pins win (above 128 too); a partial pin takes 128 on its
        unset side; no pin gives the rule."""
        from repro.configs import get_smoke_config
        from repro.models.attention import _flash_blocks

        cfg = get_smoke_config("qwen3_0_6b")
        pick = lambda **kw: _flash_blocks(
            dataclasses.replace(cfg, **kw), 8192, 1, 4, 2, 32, jnp.float32, True
        )
        assert pick() == (512, 1024)
        assert pick(attn_block_q=128, attn_block_kv=128) == (128, 128)
        assert pick(attn_block_q=256, attn_block_kv=2048) == (256, 2048)
        assert pick(attn_block_q=64) == (64, 128)
        assert pick(attn_block_kv=512) == (128, 512)

    def test_autotune_blocks_cached_and_valid(self, tmp_path):
        from repro.kernels.autotune import autotune_blocks, candidate_blocks

        cache = tmp_path / "attn_blocks.json"
        picked = autotune_blocks(
            1, 128, 2, 1, 32, has_segments=True, repeats=1, cache_path=cache,
        )
        assert picked in candidate_blocks(128)
        assert 128 % picked[0] == 0 and 128 % picked[1] == 0
        assert cache.exists()
        # second call is a pure cache hit (same pick, no new probe)
        again = autotune_blocks(
            1, 128, 2, 1, 32, has_segments=True, repeats=1, cache_path=cache,
        )
        assert again == picked


class TestPrunedGrid:
    """Scalar-prefetch grid (DESIGN.md §17): DMA-level pruning must change
    the fetch census, never the numbers — bit-exact vs the dense grid."""

    def _packed(self, key, b=2, s=256, h=4, kv=2, d=32, docs=FEW_DOCS):
        q, k, v = make_qkv(key, b, s, h, kv, d, jnp.float32)
        seg = packed_test_segments(b, s, docs)  # GQA + pad tail + all-padding row
        return q, k, v, seg

    def _packed_for(self, key, blocks):
        """Tiles above 128 run on rows of nine documents, 1,024 tokens or
        two of the widest tile."""
        if max(blocks) <= 128:
            return self._packed(key)
        return self._packed(key, s=max(1024, 2 * max(blocks)), docs=MANY_DOCS)

    def test_liveness_tables_match_tile_census(self):
        from repro.kernels.liveness import build_liveness_tables

        seg = packed_test_segments(3, 256)
        census = live_tile_counts(np.asarray(seg), 256, 64, 64)
        tables = build_liveness_tables(seg, block_q=64, block_kv=64)
        assert int(jnp.sum(tables.kv_count)) == census["segment_live"]
        assert int(jnp.sum(tables.q_count)) == census["segment_live"]
        # Row index lists live blocks ascending, clamped past the count.
        kv_idx = np.asarray(tables.kv_idx)
        kv_cnt = np.asarray(tables.kv_count)
        for ib in range(kv_idx.shape[0]):
            for qb in range(kv_idx.shape[1]):
                cnt = int(kv_cnt[ib, qb])
                row = kv_idx[ib, qb]
                assert list(row[:cnt]) == sorted(set(row[:cnt]))
                if cnt:
                    assert np.all(row[cnt:] == row[cnt - 1])
                else:
                    assert np.all(row == 0)

    @pytest.mark.parametrize(
        "blocks",
        [(64, 64), (128, 32), (128, 128), (256, 256), (256, 512), (512, 512),
         (512, 1024)],
    )
    def test_pruned_fwd_bitexact(self, blocks):
        bq, bk = blocks
        q, k, v, seg = self._packed_for(jax.random.PRNGKey(20), blocks)
        dense = flash_attention(q, k, v, seg, True, bq, bk, grid="dense")
        pruned = flash_attention(q, k, v, seg, True, bq, bk, grid="pruned")
        assert np.array_equal(np.asarray(dense), np.asarray(pruned))

    def test_pruned_fwd_bitexact_ragged_blocks(self):
        """S=200 resolves to block 40: pruning survives ragged grids."""
        q, k, v = make_qkv(jax.random.PRNGKey(21), 1, 200, 2, 2, 32, jnp.float32)
        seg = np.zeros((1, 200), np.int32)
        seg[0, :90] = 1
        seg[0, 90:170] = 2  # 30-token padding tail
        seg = jnp.asarray(seg)
        dense = flash_attention(q, k, v, seg, grid="dense")
        pruned = flash_attention(q, k, v, seg, grid="pruned")
        assert np.array_equal(np.asarray(dense), np.asarray(pruned))

    @pytest.mark.parametrize(
        "blocks", [(64, 64), (256, 256), (256, 512), (512, 512), (512, 1024)]
    )
    def test_pruned_grads_bitexact(self, blocks):
        q, k, v, seg = self._packed_for(jax.random.PRNGKey(22), blocks)
        valid = jnp.asarray((np.asarray(seg) > 0)[:, :, None, None], jnp.float32)

        def loss(grid):
            def f(q, k, v):
                out = flash_attention(q, k, v, seg, True, *blocks, grid=grid)
                return jnp.sum((out.astype(jnp.float32) * valid) ** 2)

            return f

        gd = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(loss("pruned"), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gd, gp):
            assert np.all(np.isfinite(np.asarray(b_)))
            assert np.array_equal(np.asarray(a), np.asarray(b_))

    def test_bwd_pruned_entry_direct(self):
        from repro.kernels.flash_attention import (
            segment_flash_attention_bwd_pruned,
            segment_flash_attention_pruned,
        )

        q, k, v, seg = self._packed(jax.random.PRNGKey(23))
        out, lse = segment_flash_attention_pruned(
            q, k, v, seg, interpret=True, return_residuals=True,
            block_q=64, block_kv=64,
        )
        ref_out, ref_lse = segment_flash_attention(
            q, k, v, seg, interpret=True, return_residuals=True,
            block_q=64, block_kv=64,
        )
        assert np.array_equal(np.asarray(out), np.asarray(ref_out))
        assert np.array_equal(np.asarray(lse), np.asarray(ref_lse))
        g = jax.random.normal(jax.random.PRNGKey(24), out.shape)
        pruned = segment_flash_attention_bwd_pruned(
            q, k, v, seg, out, lse, g, block_q=64, block_kv=64, interpret=True
        )
        dense = segment_flash_attention_bwd(
            q, k, v, seg, out, lse, g, block_q=64, block_kv=64, interpret=True
        )
        for a, b_ in zip(dense, pruned):
            assert np.array_equal(np.asarray(a), np.asarray(b_))

    def test_resolve_grid_matrix(self):
        from repro.kernels.ops import resolve_grid

        seg = jnp.ones((1, 8), jnp.int32)
        assert resolve_grid("pruned", None) == "dense"  # nothing to prune
        assert resolve_grid("dense", seg) == "dense"
        assert resolve_grid("pruned", seg) == "pruned"
        assert resolve_grid(None, None) == "dense"
        expected = "pruned" if jax.default_backend() == "tpu" else "dense"
        assert resolve_grid("auto", seg) == expected
        with pytest.raises(ValueError, match="grid"):
            resolve_grid("sparse", seg)

    def test_no_segments_degrades_to_dense(self):
        q, k, v = make_qkv(jax.random.PRNGKey(25), 1, 128, 2, 2, 32, jnp.float32)
        a = flash_attention(q, k, v, None, grid="pruned")
        b_ = flash_attention(q, k, v, None, grid="dense")
        assert np.array_equal(np.asarray(a), np.asarray(b_))

    def test_fetch_census_pruned_below_dense(self):
        from repro.kernels.liveness import fetched_tile_counts

        seg = packed_test_segments(3, 256)
        census = fetched_tile_counts(
            np.asarray(seg), 256, 64, 64, heads=4, kv_heads=2, head_dim=32
        )
        assert census["pruned_fetches"] < census["dense_fetches"]
        assert census["pruned_fetched_fraction"] < census["dense_fetched_fraction"]
        assert census["live_tiles"] <= census["pruned_fetches"]
        assert census["dense_fetches"] * census["kv_tile_bytes"] == (
            census["dense_fetched_bytes"]
        )

    def test_resolved_blocks_pinned_and_asserted(self):
        """select_block is not idempotent on raw requests; expect_resolved
        catches any pass fed an unresolved pair."""
        assert select_block(120, 15) == 8  # the non-idempotence witness
        bq, bk = 15, 15
        r = resolve_blocks(120, bq, bk)
        assert resolve_blocks(120, *r) == r  # fixed point after one pass
        q, k, v, seg = self._packed(jax.random.PRNGKey(26), s=120)
        with pytest.raises(AssertionError, match="not resolved"):
            segment_flash_attention(
                q, k, v, seg, block_q=15, block_kv=15,
                interpret=True, expect_resolved=True,
            )

    @pytest.mark.parametrize("s", [200, 768, 1024, 6144, 8192])
    def test_resolve_blocks_fixed_point_above_128(self, s):
        """Requests above 128 are honoured where they divide S, and one
        resolution is a fixed point, so the passes never re-resolve."""
        for req in [(256, 256), (512, 512), (512, 1024), (1024, 512), (4096, 4096)]:
            r = resolve_blocks(s, *req)
            assert resolve_blocks(s, *r) == r
            assert all(s % x == 0 and x <= max(y, 1) for x, y in zip(r, req))
            if s % req[0] == 0 and s % req[1] == 0:
                assert r == req

    def test_autotune_rekeyed_by_grid(self, tmp_path):
        from repro.kernels.autotune import autotune_blocks, shape_key

        assert shape_key(1, 128, 2, 1, 32, has_segments=True) != shape_key(
            1, 128, 2, 1, 32, has_segments=True, grid="pruned"
        )
        cache = tmp_path / "attn_blocks.json"
        a = autotune_blocks(
            1, 128, 2, 1, 32, has_segments=True, repeats=1,
            cache_path=cache, grid="dense",
        )
        b_ = autotune_blocks(
            1, 128, 2, 1, 32, has_segments=True, repeats=1,
            cache_path=cache, grid="pruned",
        )
        import json

        entries = json.loads(cache.read_text())
        keys = set(entries)
        assert any("grid.dense" in key for key in keys)
        assert any("grid.pruned" in key for key in keys)
        assert 128 % a[0] == 0 and 128 % b_[1] == 0

    def test_sharded_compile_cell(self):
        """validate_flash_sharded on the host mesh: both grid variants
        lower + compile under shard_map (the §17 dry-run contract)."""
        from repro.launch.flash_dryrun import validate_flash_sharded
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        for grid in ("dense", "pruned"):
            rec = validate_flash_sharded(
                mesh, grid, rows_per_shard=1, seq=128, heads=2, kv_heads=1,
                head_dim=32, block_q=64, block_kv=64,
            )
            assert rec["status"] == "ok", rec.get("traceback")
            assert rec["compile_s"] > 0


SSD_SWEEP = [
    # (B, S, H, P, N, chunk)
    (1, 64, 1, 8, 16, 16),
    (2, 128, 3, 8, 16, 32),
    (1, 256, 2, 16, 32, 64),
    (2, 96, 4, 8, 8, 32),  # ragged chunk boundary (96 % 32 == 0)
]


class TestSSDScan:
    @pytest.mark.parametrize("shape", SSD_SWEEP)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_sequential_ref(self, shape, dtype):
        b, s, h, p, n, chunk = shape
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
        bp = (jax.random.normal(ks[3], (b, s, n)) * 0.4).astype(dtype)
        cp = (jax.random.normal(ks[4], (b, s, n)) * 0.4).astype(dtype)
        y = ssd_scan(
            x.astype(jnp.float32), a[None, None, :] * dt, dt,
            bp.astype(jnp.float32), cp.astype(jnp.float32),
            chunk=chunk, interpret=True,
        )
        y_ref, _ = ssd_scan_ref(
            x.astype(jnp.float32), dt, a,
            bp.astype(jnp.float32), cp.astype(jnp.float32),
        )
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-3
        )

    def test_ops_wrapper(self):
        b, s, h, p, n = 1, 64, 2, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
        bp = jax.random.normal(ks[3], (b, s, n)) * 0.4
        cp = jax.random.normal(ks[4], (b, s, n)) * 0.4
        y = ssd_chunked_scan(x, dt, a, bp, cp, chunk=32)
        y_ref, _ = ssd_scan_ref(x, dt, a, bp, cp)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-3)

    def test_model_ssd_matches_kernel(self):
        """models.ssm chunked impl and the kernel agree (same math)."""
        from repro.models.ssm import ssd_chunked
        b, s, h, p, n = 2, 128, 3, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
        bp = jax.random.normal(ks[3], (b, s, n)) * 0.4
        cp = jax.random.normal(ks[4], (b, s, n)) * 0.4
        y_model, _ = ssd_chunked(x, dt, a, bp, cp, chunk=32)
        y_kernel = ssd_chunked_scan(x, dt, a, bp, cp, chunk=32)
        np.testing.assert_allclose(
            np.asarray(y_model), np.asarray(y_kernel), atol=1e-4, rtol=1e-3
        )
