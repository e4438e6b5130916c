"""Plain reference of a dense decoder language model's first training steps.

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
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_ALIGN = 256  # rows of the embedding tables: the vocabulary padded to this


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
    kind = config["model_type"]
    if kind not in ("qwen3", "olmo"):
        raise ValueError(f"the reference covers qwen3 and olmo, not {kind!r}")
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
        rms=kind == "qwen3",
        qk_norm=kind == "qwen3",
        eps=float(config.get("rms_norm_eps", 1e-6)) if kind == "qwen3" else 1e-5,
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


def flat(params: dict) -> dict:
    out = {k: v for k, v in params.items() if k != "layers"}
    for l, layer in enumerate(params["layers"]):
        out.update({f"layer{l:02d}.{k}": v for k, v in layer.items()})
    return out


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


def derived_positions(segments: np.ndarray) -> np.ndarray:
    """Position of each token within its document (runs of one segment id)."""
    b, s = segments.shape
    pos = np.zeros((b, s), np.int32)
    for r in range(b):
        start = 0
        for t in range(1, s + 1):
            if t == s or segments[r, t] != segments[r, t - 1]:
                pos[r, start:t] = np.arange(t - start)
                start = t
    return pos


def targets(tokens: np.ndarray, segments: np.ndarray, loss_mask: np.ndarray):
    """Next-token labels, and the weight of each position: 1 where the
    position and the next are real tokens of one document."""
    labels = np.concatenate([tokens[:, 1:], np.zeros_like(tokens[:, :1])], 1)
    nxt_seg = np.concatenate([segments[:, 1:], np.zeros_like(segments[:, :1])], 1)
    nxt_mask = np.concatenate([loss_mask[:, 1:], np.zeros_like(loss_mask[:, :1])], 1)
    weight = loss_mask * nxt_mask * (segments == nxt_seg)
    return labels.astype(np.int32), weight.astype(np.float32)


def _head_rows(vocab: int) -> int:
    """Rows per loss block: about 128 MiB of float32 logits."""
    rows = 1 << max(7, int(math.log2((1 << 25) / vocab)))
    return rows


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
    b, s = tokens.shape
    n = b * s
    rows = min(_head_rows(a.vocab), n)
    pad = -n % rows
    xf = jnp.pad(x.reshape(n, a.d), ((0, pad), (0, 0)))
    lab = jnp.asarray(np.pad(labels.reshape(n), (0, pad)))
    wt = jnp.asarray(np.pad(weight.reshape(n), (0, pad)))
    final = params.get("final_norm", jnp.zeros((0,), model.dtype))
    loss_sum = jnp.zeros((), F32)
    g_final = jnp.zeros(final.shape, F32)
    g_out = jnp.zeros(params["unembed"].shape, F32)
    dxs = []
    for i in range(0, n + pad, rows):
        value, (gf, gu, gx) = model.head(
            final, params["unembed"], xf[i : i + rows], lab[i : i + rows], wt[i : i + rows]
        )
        loss_sum += value
        g_final += gf.astype(F32)
        g_out += gu.astype(F32)
        dxs.append(gx)
    dx = jnp.concatenate(dxs)[:n].reshape(b, s, a.d)
    del xf, dxs
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


# -- the optimizer -------------------------------------------------------------


def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_fraction`` of the peak."""
    total = opt["total_steps"]
    warmup = max(opt["warmup_ratio"] * total, 1.0)
    if step < warmup:
        return opt["lr"] * step / warmup
    progress = min(max((step - warmup) / max(total - warmup, 1.0), 0.0), 1.0)
    floor = opt["min_lr_fraction"]
    return opt["lr"] * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * progress)))


@jax.jit
def _leaf_sq(tree):
    return jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(F32))), tree)


def _adamw_leaf(p, g, m, v, scale, lr, bc1, bc2, b1, b2, eps, wd):
    g = (g * scale).astype(p.dtype).astype(F32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p.astype(F32)
    return (p.astype(F32) - lr * delta).astype(p.dtype), m, v


_adamw = jax.jit(_adamw_leaf, donate_argnums=(0, 2, 3))


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


@jax.jit
def _diff_norm(x, y):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))


def train3(config: dict, seed: int, batches: list[dict], *, dtype=F32, drop_half=False):
    """Follow the first three training steps from the seed's weights.

    Returns the loss of each step, the norm of each leaf of the first
    gradient as the optimizer takes it (after global-norm clipping), and the
    norm of each leaf's change after three updates.  ``drop_half`` plants a
    fault: each step's loss is the mean over the first half of its token
    slots (the first half of the rows, or of the one row).
    """
    a = arch_of(config)
    opt = config["optimizer"]
    b1, b2 = opt["betas"]
    model = Model(a, dtype)
    key = jax.random.PRNGKey(seed)
    drawn = jnp.dtype(config["torch_dtype"])  # the weights as the configuration holds them

    @jax.jit
    def weights(k):
        return jax.tree.map(lambda x: x.astype(model.dtype), init_params(a, k, drawn))

    t = time.perf_counter()
    params = weights(key)
    jax.block_until_ready(params)
    took = [f"weights {time.perf_counter() - t:.1f}s"]
    moments = None
    losses, grad1 = [], {}
    for step, batch in enumerate(batches[:3], start=1):
        if drop_half:
            mask = batch["loss_mask"].copy()
            mask.reshape(-1)[mask.size // 2 :] = 0.0
            batch = dict(batch, loss_mask=mask)
        t = time.perf_counter()
        loss, grads = loss_and_grads(model, params, batch)
        losses.append(loss)
        sq = _leaf_sq(grads)
        gnorm = math.sqrt(sum(float(x) for x in jax.tree.leaves(sq)))
        scale = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
        if step == 1:
            grad1 = {k: math.sqrt(float(v)) * scale for k, v in flat(sq).items()}
        if moments is None:
            moments = jax.tree.map(lambda g: (jnp.zeros_like(g), jnp.zeros_like(g)), grads)
        lr = learning_rate(step, opt)
        consts = (scale, lr, 1 - b1**step, 1 - b2**step, b1, b2, opt["eps"], opt["weight_decay"])
        p_leaves, treedef = jax.tree.flatten(params)
        g_leaves = jax.tree.leaves(grads)
        m_leaves = treedef.flatten_up_to(moments)
        del params, grads, moments
        out = [_adamw(p, g, m, v, *consts) for p, g, (m, v) in zip(p_leaves, g_leaves, m_leaves)]
        del p_leaves, g_leaves, m_leaves
        params = treedef.unflatten([o[0] for o in out])
        moments = treedef.unflatten([(o[1], o[2]) for o in out])
        del out
        jax.block_until_ready(params)
        took.append(f"step {step} {batch['tokens'].shape} {time.perf_counter() - t:.1f}s")
    del moments
    start = flat(weights(key))
    change = {k: float(_diff_norm(v, start[k])) for k, v in flat(params).items()}
    print(f"[reference] {jnp.dtype(dtype).name}: {'; '.join(took)}", file=sys.stderr, flush=True)
    return {"loss": losses, "grad1": grad1, "change": change}
