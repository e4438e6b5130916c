"""Primitive layers: norms, rotary embeddings, MLP, init, cross-entropy."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]


def truncated_normal_init(key, shape, scale: float, dtype) -> jax.Array:
    stddev = scale / max(math.sqrt(shape[0]), 1.0)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return truncated_normal_init(key, (d_in, d_out), 1.0, dtype)


# -- norms ---------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(dt)


def layer_norm(
    x: jax.Array,
    weight: jax.Array | None,
    bias: jax.Array | None,
    eps: float = 1e-5,
) -> jax.Array:
    """Parametric LN, or OLMo's non-parametric LN when weight/bias are None."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def make_norm_params(key, cfg, dtype) -> Params:
    if cfg.norm == "ln_nonparam":
        return {}
    return {"scale": jnp.ones((cfg.d_model,), dtype=dtype)}


def apply_norm(params: Params, x: jax.Array, cfg) -> jax.Array:
    if cfg.norm == "rms":
        return rms_norm(x, params.get("scale"))
    if cfg.norm == "ln":
        return layer_norm(x, params.get("scale"), None)
    return layer_norm(x, None, None)  # non-parametric (OLMo)


# -- rotary --------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
    return 1.0 / (theta**exponent)  # (d_head/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, n_heads, d_head); positions: (..., seq)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., seq, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- MLP -----------------------------------------------------------------------


def act_fn(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def make_mlp_params(key, d_model: int, d_ff: int, gated: bool, dtype) -> Params:
    keys = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(keys[0], d_model, d_ff, dtype),
        "w_out": dense_init(keys[1], d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(keys[2], d_model, d_ff, dtype)
    return p


def apply_mlp(params: Params, x: jax.Array, act: str, gated: bool) -> jax.Array:
    """The feed-forward block; its operations carry the ``mlp`` scope."""
    with jax.named_scope("mlp"):
        h = x @ params["w_in"]
        if gated:
            h = act_fn(act)(x @ params["w_gate"]) * h
        else:
            h = act_fn(act)(h)
        return h @ params["w_out"]


# -- losses --------------------------------------------------------------------


def masked_cross_entropy(
    logits: jax.Array,  # (..., seq, vocab)
    labels: jax.Array,  # (..., seq) int32
    mask: jax.Array,  # (..., seq) float — 1 on valid targets
    *,
    fp32: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (loss_sum, token_count) — the Eq. 2 accumulation primitives.

    Deliberately returns the *sum* (not mean) so the trainer can apply
    sample-/token-level scaling per the selected ODB mode.
    """
    if fp32:
        logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - picked) * mask
    return jnp.sum(nll), jnp.sum(mask)
