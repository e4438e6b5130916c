"""What every plain reference of the benchmark shares.

A configuration file names its reference (``"reference": "<name>"``), the
module ``bench/references/<name>.py``, which imports nothing of the program.
Its ``train3(config, seed, batches, *, dtype=float32, drop_half=False)``
draws the weights from the seed and follows the training job's first three
steps on the batches the program trained on; ``dtype=bfloat16`` is the
control that `correct` must reject, ``drop_half`` a planted fault.  What
depends on the architecture (its sizes, weights and layers) is the
module's own; what follows is the same for every one:

  derived_positions, targets   positions and next-token labels of a packed batch
  head_loss                    the loss over the rows of the last hidden
                               state, in blocks of rows
  learning_rate, adamw_leaf    the optimizer's schedule and update
  flat                         leaves by the names the comparison uses
  follow3                      the three steps: loss, first gradient as the
                               optimizer takes it, change after three updates

Leaf names (``flat``), as ``harness.leaf_norms`` names the program's: a
leaf outside the layers by its key (``embed``, ``unembed``, ``final_norm``),
a layer's as ``layerNN.<path>``, ``NN`` the layer's index in the model and
``<path>`` the keys inside the layer joined by ``.``: the attention mixer's
and the dense MLP's leaves by their own key (``wq``, ``w_in``), the norms as
``attn_norm`` and ``mlp_norm``, any other group kept (``moe.w_in``,
``moe.shared.w_in``).
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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


def head_rows(vocab: int) -> int:
    """Rows per loss block: about 128 MiB of float32 logits."""
    return 1 << max(7, int(math.log2((1 << 25) / vocab)))


def head_loss(head, final, unembed, x, labels: np.ndarray, weight: np.ndarray, vocab: int):
    """The summed loss of the rows of ``x`` (B, S, d), in blocks of
    ``head_rows(vocab)`` rows.  ``head(final, unembed, rows, labels, weight)``
    gives one block's summed loss and its gradients by ``final``,
    ``unembed`` and ``rows``.  Returns the loss sum, the float32 gradients
    of ``final`` and ``unembed``, and the gradient by ``x``."""
    b, s, d = x.shape
    n = b * s
    rows = min(head_rows(vocab), n)
    pad = -n % rows
    xf = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
    lab = jnp.asarray(np.pad(labels.reshape(n), (0, pad)))
    wt = jnp.asarray(np.pad(weight.reshape(n), (0, pad)))
    loss_sum = jnp.zeros((), F32)
    g_final = jnp.zeros(final.shape, F32)
    g_out = jnp.zeros(unembed.shape, F32)
    dxs = []
    for i in range(0, n + pad, rows):
        value, (gf, gu, gx) = head(final, unembed, xf[i : i + rows], lab[i : i + rows], wt[i : i + rows])
        loss_sum += value
        g_final += gf.astype(F32)
        g_out += gu.astype(F32)
        dxs.append(gx)
    dx = jnp.concatenate(dxs)[:n].reshape(b, s, d)
    return loss_sum, g_final, g_out, dx


def flat(params: dict) -> dict:
    """Leaves by name: a key outside ``params["layers"]`` as it is, layer
    ``l``'s as ``layer{l:02d}.<key>``; nested keys joined by ``.``."""

    def walk(prefix, tree):
        if not isinstance(tree, dict):
            yield prefix, tree
            return
        for k, v in tree.items():
            yield from walk(f"{prefix}.{k}" if prefix else k, v)

    out = dict(walk("", {k: v for k, v in params.items() if k != "layers"}))
    for l, layer in enumerate(params["layers"]):
        out.update(walk(f"layer{l:02d}", layer))
    return out


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


def adamw_leaf(p, g, m, v, scale, lr, bc1, bc2, b1, b2, eps, wd):
    """One AdamW update of one leaf, the gradient clipped by ``scale`` and
    rounded to the leaf's type; the moments in float32."""
    g = (g * scale).astype(p.dtype).astype(F32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p.astype(F32)
    return (p.astype(F32) - lr * delta).astype(p.dtype), m, v


_adamw = jax.jit(adamw_leaf, donate_argnums=(0, 2, 3))


@jax.jit
def _diff_norm(x, y):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))


def follow3(config: dict, seed: int, batches: list[dict], weights, loss_and_grads, *,
            dtype=F32, drop_half=False) -> dict:
    """The first three training steps from the seed's weights.

    ``weights(key)`` gives the parameters (``{"layers": [...], ...}``) in
    the precision computed in; ``loss_and_grads(params, batch)`` one step's
    mean loss and its float32 gradient.  Returns the loss of each step, the
    norm of each leaf of the first gradient as the optimizer takes it (after
    global-norm clipping), and the norm of each leaf's change after three
    updates.  ``drop_half`` plants a fault: each step's loss is the mean over
    the first half of its token slots (the first half of the rows, or of the
    one row).
    """
    opt = config["optimizer"]
    b1, b2 = opt["betas"]
    key = jax.random.PRNGKey(seed)
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
        loss, grads = loss_and_grads(params, batch)
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
