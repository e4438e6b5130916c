"""The language model: embeddings + scanned stack + heads + entry points.

Entry points (consumed by launch/ and train/):

  * ``forward(params, batch)``       → logits           (train / encoder)
  * ``loss_sums(params, batch)``     → (loss_sum, token_count)  — the Eq. 2
    accumulation primitives (trainer applies ODB loss scaling);
  * ``prefill(params, tokens, max_len)`` → (logits, caches)
  * ``decode_step(params, caches, tokens, cache_index)`` → (logits, caches)

Batches are dicts: ``tokens`` (B, S) int32 *or* ``embeds`` (B, S, d) for
stubbed-frontend archs (hubert), plus ``labels``, ``loss_mask`` and optional
``positions`` / ``segments`` (packed layout).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.blocks import (
    init_unit_cache,
    make_unit_params,
    stack_params,
    stack_plan,
    unit_forward,
)
from repro.models.config import ArchConfig
from repro.models.layers import (
    apply_norm,
    dense_init,
    make_norm_params,
    masked_cross_entropy,
)

Params = dict[str, Any]

VOCAB_ALIGN = 256  # pad vocab so TP=16 divides and MXU lanes align


def padded_vocab(vocab: int) -> int:
    return (vocab + VOCAB_ALIGN - 1) // VOCAB_ALIGN * VOCAB_ALIGN


def _sp_constraint(x, mesh):
    """Sequence-parallel sharding constraint on the residual stream:
    (B, S, d) → P(dp, "model", None).  GSPMD inserts the all-gather on
    entering attention/FFN and the reduce-scatter on exit (the standard SP
    exchange), shrinking resident activations, norm intermediates and saved
    remat carries by the TP degree (§Perf lever)."""
    if mesh is None or "model" not in mesh.axis_names:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import batch_dp_axes

    dp = batch_dp_axes(x.shape[0], mesh)
    if x.shape[1] % mesh.shape["model"] != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, "model", None))
    )


@dataclasses.dataclass
class LM:
    cfg: ArchConfig
    mesh: Any = None

    def __post_init__(self):
        self.plan = stack_plan(self.cfg)
        self.dtype = jnp.dtype(self.cfg.dtype)
        impl = self.cfg.attn_impl
        if impl not in ("xla", "flash", "auto"):
            raise ValueError(
                f"attn_impl {impl!r} not in ('xla', 'flash', 'auto')"
            )
        if impl == "flash" and self.cfg.attn_kind == "mla":
            raise ValueError(
                "attn_impl='flash' requires GQA-layout attention; MLA's "
                "latent score decomposition trains on the XLA blockwise path"
            )

    # -- init ------------------------------------------------------------------
    def init(self, rng) -> Params:
        cfg = self.cfg
        vp = padded_vocab(cfg.vocab_size)
        k_embed, k_unembed, k_norm, k_prefix, k_stack = jax.random.split(rng, 5)
        params: Params = {"final_norm": make_norm_params(k_norm, cfg, self.dtype)}
        if not cfg.input_embeds:
            params["embed"] = dense_init(k_embed, vp, cfg.d_model, self.dtype)
        params["unembed"] = dense_init(k_unembed, cfg.d_model, vp, self.dtype)
        if self.plan.prefix_layers:
            keys = jax.random.split(k_prefix, len(self.plan.prefix_layers))
            params["prefix"] = [
                make_unit_params(keys[i], cfg, (l,), self.dtype)
                for i, l in enumerate(self.plan.prefix_layers)
            ]
        keys = jax.random.split(k_stack, self.plan.n_units)
        per_unit = [
            make_unit_params(keys[u], cfg, self.plan.unit_layers[u], self.dtype)
            for u in range(self.plan.n_units)
        ]
        params["stack"] = stack_params(per_unit)
        return params

    def abstract_params(self, rng=None) -> Params:
        """Shape/dtype-only params (no allocation) — for the dry-run."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # -- core stack ---------------------------------------------------------------
    def _embed(self, params: Params, batch: dict) -> jax.Array:
        if self.cfg.input_embeds:
            return batch["embeds"].astype(self.dtype)
        return params["embed"][batch["tokens"]]

    def _positions_segments(self, batch: dict, s: int):
        tokens_like = batch.get("tokens", batch.get("embeds"))
        b = tokens_like.shape[0]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        segments = batch.get("segments")
        return positions, segments

    def _run_stack(
        self, params, x, positions, segments, caches=None, cache_index=None,
        dest_slot=None,
    ):
        cfg, plan, mesh = self.cfg, self.plan, self.mesh

        new_prefix_caches = []
        if plan.prefix_layers:
            for i, l in enumerate(plan.prefix_layers):
                pc = caches["prefix"][i] if caches else None
                x, nc = unit_forward(
                    params["prefix"][i], x, cfg, (l,), positions, segments,
                    pc, cache_index, mesh, dest_slot=dest_slot,
                )
                new_prefix_caches.append(nc)

        unit_layers = plan.unit_layers[0] if plan.unit_layers else ()

        def scan_body(carry, xs):
            h = carry
            unit_params, unit_cache = xs
            if cfg.sequence_sharding:
                h = _sp_constraint(h, mesh)
            h, new_cache = unit_forward(
                unit_params, h, cfg, unit_layers, positions, segments,
                unit_cache, cache_index, mesh, dest_slot=dest_slot,
            )
            if cfg.sequence_sharding:
                h = _sp_constraint(h, mesh)
            return h, new_cache

        body = scan_body
        if cfg.remat == "full":
            body = jax.checkpoint(scan_body)
        elif cfg.remat == "dots":
            body = jax.checkpoint(
                scan_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )

        stack_caches = caches["stack"] if caches else None
        x, new_stack_caches = jax.lax.scan(
            body, x, (params["stack"], stack_caches)
        )
        new_caches = None
        if caches is not None:
            new_caches = {"prefix": new_prefix_caches, "stack": new_stack_caches}
        return x, new_caches

    # -- public entry points ---------------------------------------------------------
    def forward(self, params: Params, batch: dict) -> jax.Array:
        return self._logits(params, self._hidden(params, batch))

    def _hidden(self, params: Params, batch: dict) -> jax.Array:
        x = self._embed(params, batch)
        positions, segments = self._positions_segments(batch, x.shape[1])
        x, _ = self._run_stack(params, x, positions, segments)
        return x

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        x = apply_norm(params["final_norm"], x, self.cfg)
        logits = x @ params["unembed"]
        if self.cfg.logits_fp32:
            logits = logits.astype(jnp.float32)
        vp = padded_vocab(self.cfg.vocab_size)
        if vp != self.cfg.vocab_size:
            pad_bias = jnp.where(
                jnp.arange(vp) < self.cfg.vocab_size, 0.0, -1e9
            ).astype(logits.dtype)
            logits = logits + pad_bias
        return logits

    def loss_sums(self, params: Params, batch: dict):
        """(loss_sum, token_count) over valid targets — Eq. 2 primitives.

        The final norm, the unembedding and the cross-entropy carry the
        ``lm_loss`` scope."""
        x = self._hidden(params, batch)
        with jax.named_scope("lm_loss"):
            return masked_cross_entropy(
                self._logits(params, x), batch["labels"], batch["loss_mask"],
                fp32=self.cfg.logits_fp32,
            )

    # -- serving ----------------------------------------------------------------------
    def init_caches(self, batch: int, max_len: int) -> Params:
        plan, cfg = self.plan, self.cfg
        cache_dtype = self.dtype
        prefix = [
            init_unit_cache(cfg, (l,), batch, max_len, cache_dtype)
            for l in plan.prefix_layers
        ]
        per_unit = [
            init_unit_cache(cfg, plan.unit_layers[u], batch, max_len, cache_dtype)
            for u in range(plan.n_units)
        ]
        return {"prefix": prefix, "stack": stack_params(per_unit)}

    def prefill(self, params: Params, tokens: jax.Array, max_len: int):
        """Encode a prompt, filling caches; returns (last-token logits, caches)."""
        b, s = tokens.shape
        caches = self.init_caches(b, max_len)
        batch = {"tokens": tokens}
        x = self._embed(params, batch)
        positions, segments = self._positions_segments(batch, s)
        x, caches = self._run_stack(
            params, x, positions, segments, caches, jnp.array(0, jnp.int32)
        )
        x = apply_norm(params["final_norm"], x[:, -1:], self.cfg)
        logits = (x @ params["unembed"]).astype(jnp.float32)
        return logits, caches

    def prefill_packed(
        self,
        params: Params,
        caches: Params,
        tokens: jax.Array,  # (R, S) packed-segment stream
        positions: jax.Array,  # (R, S) within-segment positions
        segments: jax.Array,  # (R, S) 0 = padding, >=1 per request
        dest_slot: jax.Array,  # (R, S) cache row per stream position
    ):
        """Packed-segment prefill scattering K/V into per-request cache slots.

        The continuous-batching serving path (DESIGN.md §12): several
        admitted prompts share one packed stream — attention is the
        segment-masked train-path route (Pallas flash when routed), so a
        mixed-length admission cohort prefills in one fixed-shape call —
        while each layer's roped K/V lands in the cache row named by
        ``dest_slot`` at its within-segment position.  Padding positions
        point ``dest_slot`` out of range so their writes drop.  Returns the
        full-stream logits (gathering per-segment last positions is the
        caller's concern: the jitted serve step fuses the gather).
        """
        x = self._embed(params, {"tokens": tokens})
        x, caches = self._run_stack(
            params, x, positions, segments, caches, None, dest_slot=dest_slot
        )
        x = apply_norm(params["final_norm"], x, self.cfg)
        logits = (x @ params["unembed"]).astype(jnp.float32)
        return logits, caches

    def decode_step_slots(
        self,
        params: Params,
        caches: Params,
        tokens: jax.Array,  # (B, 1) — one pending token per cache slot
        lengths: jax.Array,  # (B,) int32: per-slot tokens already cached
    ):
        """One decode step against per-slot cache frontiers.

        The continuous-batching analogue of :meth:`decode_step`: every cache
        row (slot) sits at its own depth ``lengths[i]``, so admission and
        eviction never change the step's shape — the jitted decode compiles
        exactly once for ``(B, 1)`` regardless of which requests occupy the
        slots (the compile-once contract, DESIGN.md §12).
        """
        b, s = tokens.shape
        x = self._embed(params, {"tokens": tokens})
        positions = lengths.astype(jnp.int32)[:, None] + jnp.arange(
            s, dtype=jnp.int32
        )
        x, new_caches = self._run_stack(
            params, x, positions, None, caches, lengths
        )
        x = apply_norm(params["final_norm"], x, self.cfg)
        logits = (x @ params["unembed"]).astype(jnp.float32)
        return logits, new_caches

    def decode_step(
        self,
        params: Params,
        caches: Params,
        tokens: jax.Array,  # (B, 1)
        cache_index: jax.Array,  # scalar int32: tokens already cached
    ):
        b, s = tokens.shape
        batch = {"tokens": tokens}
        x = self._embed(params, batch)
        positions = jnp.broadcast_to(
            cache_index.astype(jnp.int32), (b, s)
        ) + jnp.arange(s, dtype=jnp.int32)
        x, new_caches = self._run_stack(
            params, x, positions, None, caches, cache_index
        )
        x = apply_norm(params["final_norm"], x, self.cfg)
        logits = (x @ params["unembed"]).astype(jnp.float32)
        return logits, new_caches


def shift_labels(
    tokens: jax.Array,
    loss_mask: jax.Array,
    pad_id: int = 0,
    segments: jax.Array | None = None,
):
    """Next-token targets: labels[t] = tokens[t+1]; last position masked.

    With ``segments`` (packed layout) a position is additionally masked when
    the next token belongs to a different segment — otherwise the last token
    of each packed sample would be trained to predict its row-neighbour's
    first token (cross-sample label contamination).
    """
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], pad_id)], axis=1
    )
    mask = loss_mask * jnp.concatenate(
        [loss_mask[:, 1:], jnp.zeros_like(loss_mask[:, :1])], axis=1
    )
    if segments is not None:
        next_seg = jnp.concatenate(
            [segments[:, 1:], jnp.zeros_like(segments[:, :1])], axis=1
        )
        mask = mask * (segments == next_seg).astype(mask.dtype)
    return labels, mask
