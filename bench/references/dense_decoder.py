"""Plain reference of a dense decoder language model's first training steps
(``"reference": "dense_decoder"``).

It imports nothing of the program.  It reads the configuration file's sizes,
draws the weights from the seed by the recipe the configuration states, and
follows the training job's first steps on the batches the program trained
on: the loss, the gradient and the AdamW update, in straightforward
``jax.numpy``.  In float32 every matrix product runs at ``"highest"``
precision.  The same code in bfloat16 (parameters, activations and products
at default precision, updates rounded to bfloat16) is the control that
`correct` must reject.

The step is computed in blocks so that it fits beside nothing else on one
chip: layer by layer (the inputs of each layer are kept, the backward pass
recomputes the layer), attention by blocks of queries, and the loss by
blocks of rows.

Model (Qwen3 and OLMo families, the configurations' ``model_type``):
pre-norm blocks ``x += attn(norm(x)); x += mlp(norm(x))``, grouped-query
attention with rotary positions (halves rotated), causal within each packed
document, a SwiGLU MLP, a final norm and an untied output projection.  Qwen3
uses RMS norms with a scale (eps ``rms_norm_eps``) and RMS-normalises each
query and key head; OLMo uses layer norms without scale or bias (eps 1e-5).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from reference import F32, derived_positions, follow3, head_loss, targets

VOCAB_ALIGN = 256  # rows of the embedding tables: the vocabulary padded to this

# The model types this reference covers, each with what the program's
# ``ArchConfig`` must be for it (``cell.arch_config`` checks it): Qwen3 has RMS
# norms and normalises each query and key head, OLMo non-parametric layer norms.
MODEL_TYPES = {
    "qwen3": {"attn_kind": "gqa", "norm": "rms", "qk_norm": True},
    "olmo": {"attn_kind": "gqa", "norm": "ln_nonparam", "qk_norm": False},
}


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rows: int  # embedding rows (padded vocabulary)
    rope_theta: float
    rms: bool  # RMS norm with scale (Qwen3) or non-parametric layer norm (OLMo)
    qk_norm: bool
    eps: float


def arch_of(config: dict) -> Arch:
    kind = MODEL_TYPES.get(config["model_type"])
    if kind is None:
        raise ValueError(f"the reference covers {sorted(MODEL_TYPES)}, not {config['model_type']!r}")
    rms = kind["norm"] == "rms"
    if config.get("tie_word_embeddings"):
        raise ValueError("the reference holds an untied output projection")
    heads = config["num_attention_heads"]
    vocab = config["vocab_size"]
    return Arch(
        d=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=heads,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        ff=config["intermediate_size"],
        vocab=vocab,
        rows=-(-vocab // VOCAB_ALIGN) * VOCAB_ALIGN,
        rope_theta=float(config["rope_theta"]),
        rms=rms,
        qk_norm=kind["qk_norm"],
        eps=float(config.get("rms_norm_eps", 1e-6)) if rms else 1e-5,
    )


# -- weights -------------------------------------------------------------------


def _normal(key, shape):
    """Truncated normal in [-2, 2] over sqrt(fan_in)."""
    scale = 1.0 / max(math.sqrt(shape[0]), 1.0)
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * scale


def init_params(a: Arch, key, dtype) -> dict:
    """The configuration's weights from ``key``, in the order the recipe draws
    them: the key splits into (embedding, output, final norm, -, layers); each
    layer's key into (norm, attention, norm, MLP); attention's into (q, k, v,
    o) and the MLP's into (in, out, gate).  Norm scales start at 1."""
    k_embed, k_out, _, _, k_layers = jax.random.split(key, 5)
    ones = lambda n: jnp.ones((n,), dtype)
    params = {
        "embed": _normal(k_embed, (a.rows, a.d)).astype(dtype),
        "unembed": _normal(k_out, (a.d, a.rows)).astype(dtype),
        "layers": [],
    }
    if a.rms:
        params["final_norm"] = ones(a.d)
    for k_unit in jax.random.split(k_layers, a.layers):
        (k_layer,) = jax.random.split(k_unit, 1)
        _, k_attn, _, k_mlp = jax.random.split(k_layer, 4)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        ki, kout, kg = jax.random.split(k_mlp, 3)
        hd, kvd = a.heads * a.head_dim, a.kv_heads * a.head_dim
        layer = {
            "wq": _normal(kq, (a.d, hd)),
            "wk": _normal(kk, (a.d, kvd)),
            "wv": _normal(kv, (a.d, kvd)),
            "wo": _normal(ko, (hd, a.d)),
            "w_in": _normal(ki, (a.d, a.ff)),
            "w_out": _normal(kout, (a.ff, a.d)),
            "w_gate": _normal(kg, (a.d, a.ff)),
        }
        layer = {k: v.astype(dtype) for k, v in layer.items()}
        if a.rms:
            layer["attn_norm"] = ones(a.d)
            layer["mlp_norm"] = ones(a.d)
        if a.qk_norm:
            layer["q_norm"] = ones(a.head_dim)
            layer["k_norm"] = ones(a.head_dim)
        params["layers"].append(layer)
    return params


# -- the model -----------------------------------------------------------------


class Model:
    """One precision of the reference: ``float32`` at "highest" precision, or
    ``bfloat16`` (the control)."""

    def __init__(self, a: Arch, dtype=F32):
        self.a = a
        self.dtype = jnp.dtype(dtype)
        self.precision = (
            jax.lax.Precision.HIGHEST if self.dtype == F32 else jax.lax.Precision.DEFAULT
        )
        self.layer_fwd = jax.jit(self._layer)
        self.layer_vjp = jax.jit(self._layer_vjp)
        self.head = jax.jit(jax.value_and_grad(self._head, argnums=(0, 1, 2)))
        self.embed_grad = jax.jit(
            lambda dx, tokens: jnp.zeros((a.rows, a.d), F32).at[tokens.reshape(-1)].add(
                dx.reshape(-1, a.d).astype(F32)
            )
        )

    def mm(self, x, w):
        return jnp.matmul(x, w, precision=self.precision)

    def norm(self, x, scale):
        x32 = x.astype(F32)
        if self.a.rms:
            y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.a.eps)
            y = y * scale.astype(F32)
        else:
            mu = jnp.mean(x32, -1, keepdims=True)
            var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
            y = (x32 - mu) * jax.lax.rsqrt(var + self.a.eps)
        return y.astype(self.dtype)

    def rms_head(self, x, scale):
        x32 = x.astype(F32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.a.eps)
        return (y * scale.astype(F32)).astype(self.dtype)

    def rope(self, x, pos):
        half = self.a.head_dim // 2
        freqs = 1.0 / self.a.rope_theta ** (jnp.arange(0, half, dtype=F32) * 2 / self.a.head_dim)
        ang = pos[..., None].astype(F32) * freqs  # (B, S, half)
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(self.dtype)

    def attention(self, q, k, v, seg):
        """Softmax attention of each query over the keys of its own document
        at or before it (positions with segment 0 are padding and attend
        only to themselves).  Queries go in blocks, each recomputed in the
        backward pass."""
        a = self.a
        b, s = q.shape[:2]
        group = a.heads // a.kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        idx = jnp.arange(s)
        blk = next(c for c in (512, 256, 128, s) if s % c == 0)

        @jax.checkpoint
        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
            si = jax.lax.dynamic_slice_in_dim(seg, i * blk, blk, axis=1)
            ii = jax.lax.dynamic_slice_in_dim(idx, i * blk, blk)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=self.precision)
            scores = scores.astype(F32) / math.sqrt(a.head_dim)
            same = (si[:, :, None] == seg[:, None, :]) & (seg[:, None, :] > 0)
            allowed = (same & (idx[None, None, :] <= ii[None, :, None])) | (
                idx[None, None, :] == ii[None, :, None]
            )
            scores = jnp.where(allowed[:, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=self.precision)

        out = jax.lax.map(block, jnp.arange(s // blk))  # (n, B, blk, H, dh)
        return jnp.moveaxis(out, 0, 1).reshape(b, s, a.heads, a.head_dim)

    def _layer(self, p, x, pos, seg):
        a = self.a
        b, s, _ = x.shape
        h = self.norm(x, p.get("attn_norm"))
        q = self.mm(h, p["wq"]).reshape(b, s, a.heads, a.head_dim)
        k = self.mm(h, p["wk"]).reshape(b, s, a.kv_heads, a.head_dim)
        v = self.mm(h, p["wv"]).reshape(b, s, a.kv_heads, a.head_dim)
        if a.qk_norm:
            q, k = self.rms_head(q, p["q_norm"]), self.rms_head(k, p["k_norm"])
        q, k = self.rope(q, pos), self.rope(k, pos)
        o = self.attention(q, k, v, seg).reshape(b, s, a.heads * a.head_dim)
        x = x + self.mm(o, p["wo"])
        h = self.norm(x, p.get("mlp_norm"))
        return x + self.mm(jax.nn.silu(self.mm(h, p["w_gate"])) * self.mm(h, p["w_in"]), p["w_out"])

    def _layer_vjp(self, p, x, pos, seg, dy):
        _, pull = jax.vjp(lambda p_, x_: self._layer(p_, x_, pos, seg), p, x)
        return pull(dy)

    def _head(self, final_scale, unembed, x, labels, weight):
        """Summed next-token loss of one block of rows (the padded vocabulary
        rows are not part of the model's vocabulary)."""
        h = self.norm(x, final_scale)
        logits = self.mm(h, unembed[:, : self.a.vocab]).astype(F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * weight)


def loss_and_grads(model: Model, params: dict, batch: dict):
    """Mean next-token loss of one step and its gradient (float32 leaves)."""
    a = model.a
    tokens, seg = batch["tokens"], batch["segments"]
    pos = jnp.asarray(derived_positions(seg))
    labels, weight = targets(tokens, seg, batch["loss_mask"])
    count = float(weight.sum())
    seg_d = jnp.asarray(seg)
    x = params["embed"][jnp.asarray(tokens)]
    inputs = []
    for p in params["layers"]:
        inputs.append(x)
        x = model.layer_fwd(p, x, pos, seg_d)
    final = params.get("final_norm", jnp.zeros((0,), model.dtype))
    loss_sum, g_final, g_out, dx = head_loss(model.head, final, params["unembed"], x, labels, weight, a.vocab)
    g_layers = [None] * a.layers
    for l in reversed(range(a.layers)):
        gp, dx = model.layer_vjp(params["layers"][l], inputs[l], pos, seg_d, dx)
        g_layers[l] = {k: v.astype(F32) for k, v in gp.items()}
        inputs[l] = None
    grads = {
        "embed": model.embed_grad(dx, jnp.asarray(tokens)),
        "unembed": g_out,
        "layers": g_layers,
    }
    if "final_norm" in params:
        grads["final_norm"] = g_final
    grads = jax.tree.map(lambda g: g / count, grads)
    return float(loss_sum) / count, grads


def train3(config: dict, seed: int, batches: list[dict], *, dtype=F32, drop_half=False):
    """The first three steps (``reference.follow3``) of this model."""
    a = arch_of(config)
    model = Model(a, dtype)
    drawn = jnp.dtype(config["torch_dtype"])  # the weights as the configuration holds them

    @jax.jit
    def weights(k):
        return jax.tree.map(lambda x: x.astype(model.dtype), init_params(a, k, drawn))

    return follow3(config, seed, batches, weights, functools.partial(loss_and_grads, model),
                   dtype=dtype, drop_half=drop_half)
