"""AdamW + cosine schedule + global-norm clipping, from scratch (no optax).

Matches the paper's training hyperparameters (§3.1): AdamW, cosine decay,
lr 1e-5, warmup_ratio 0.03, grad-clip 4.0, bf16 compute.  Moments may be
stored in bf16 (``moment_dtype``) — the memory lever that makes the 480B/671B
archs fit the HBM budget (EXPERIMENTS.md §Dry-run).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Params = Any


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 1e-5
    warmup_ratio: float = 0.03
    total_steps: int = 10_000
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 4.0
    moment_dtype: str = "float32"  # "bfloat16" for the giants
    min_lr_fraction: float = 0.1


def cosine_lr(step, cfg: OptimizerConfig):
    warmup = jnp.maximum(cfg.warmup_ratio * cfg.total_steps, 1.0)
    warm = step / warmup
    progress = jnp.clip((step - warmup) / jnp.maximum(cfg.total_steps - warmup, 1.0), 0.0, 1.0)
    cos = cfg.min_lr_fraction + (1 - cfg.min_lr_fraction) * 0.5 * (
        1.0 + jnp.cos(jnp.pi * progress)
    )
    return cfg.lr * jnp.where(step < warmup, warm, cos)


def init_opt_state(params: Params, cfg: OptimizerConfig) -> dict:
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dtype=mdt)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    sq = jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), tree)
    return jnp.sqrt(jax.tree_util.tree_reduce(jnp.add, sq, jnp.zeros((), jnp.float32)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def adamw_update(params: Params, grads: Params, opt_state: dict, cfg: OptimizerConfig):
    """One AdamW step; returns (new_params, new_opt_state, metrics).  The
    clip and the update carry the ``adamw`` scope."""
    with jax.named_scope("adamw"):
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        step = opt_state["step"] + 1
        lr = cosine_lr(step.astype(jnp.float32), cfg)
        b1, b2 = cfg.betas
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        mdt = jnp.dtype(cfg.moment_dtype)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            mh = m32 / bc1
            vh = v32 / bc2
            delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - lr * delta
            return newp.astype(p.dtype), m32.astype(mdt), v32.astype(mdt)

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(opt_state["m"])
        flat_v = jax.tree_util.tree_leaves(opt_state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return (
            new_p,
            {"m": new_m, "v": new_v, "step": step},
            {"lr": lr, "grad_norm": gnorm},
        )
