"""Mamba-2 chunked SSD scan — Pallas TPU kernel.

Fuses one SSD chunk step (within-chunk quadratic term + carried-state term +
state update) per grid step.  grid = (batch, heads, num_chunks); the chunk
axis is sequential ("arbitrary") and carries the (P × N) SSM state in VMEM
scratch, so the state never round-trips HBM between chunks — the TPU
analogue of the fused CUDA chunk scan in the Mamba-2 reference.

Inputs are pre-projected (the surrounding block computes x/B/C/dt):
  x   (B, S, H, P)   — per-head inputs
  adt (B, S, H)      — a·dt (negative; pre-multiplied decay exponents)
  dt  (B, S, H)      — positive step sizes
  b_p (B, S, N)      — state input projection (ngroups=1)
  c_p (B, S, N)      — state output projection
Output: y (B, S, H, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (Q, P)
    adt_ref,  # (Q, 1)
    dt_ref,  # (Q, 1)
    b_ref,  # (Q, N)
    c_ref,  # (Q, N)
    y_ref,  # (Q, P)
    state,  # scratch (P, N) f32
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state[...])

    x = x_ref[...].astype(jnp.float32)  # (Q, P)
    adt = adt_ref[...][:, 0].astype(jnp.float32)  # (Q,)
    dt = dt_ref[...][:, 0].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)  # (Q, N)
    c = c_ref[...].astype(jnp.float32)

    acs = jnp.cumsum(adt)  # (Q,)
    # within-chunk decay matrix L[i, j] = exp(sum_{j<k<=i} adt_k), lower-tri
    diff = acs[:, None] - acs[None, :] + adt[None, :]  # = Σ_{j<=k<=i}? see below
    # acs_i - acs_j = Σ_{j<k<=i} adt_k  (for i >= j)
    diff = acs[:, None] - acs[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    l_mat = jnp.where(tri, jnp.exp(diff), 0.0)  # (Q, Q)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # (Q, Q)
    w = l_mat * scores * dt[None, :]
    y_diag = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))  # (Q, P)

    s = state[...]  # (P, N)
    y_off = jnp.exp(acs)[:, None] * jax.lax.dot_general(
        c, s, (((1,), (1,)), ((), ()))
    )  # (Q, P)

    # state update: s' = exp(acs_last)·s + Σ_q (chunk_decay_q·dt_q)·x_q ⊗ B_q
    chunk_decay = jnp.exp(acs[-1] - acs) * dt  # (Q,)
    xw = x * chunk_decay[:, None]  # (Q, P)
    s_new = jnp.exp(acs[-1]) * s + jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ()))
    )  # (P, N)
    state[...] = s_new
    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    adt: jax.Array,  # (B, S, H)
    dt: jax.Array,  # (B, S, H)
    b_p: jax.Array,  # (B, S, N)
    c_p: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bsz, s, h, p = x.shape
    n = b_p.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    grid = (bsz, h, nc)

    x_spec = pl.BlockSpec((None, chunk, None, p), lambda ib, ih, ic: (ib, ic, ih, 0))
    sc_spec = pl.BlockSpec((None, chunk, None, 1), lambda ib, ih, ic: (ib, ic, ih, 0))
    bn_spec = pl.BlockSpec((None, chunk, n), lambda ib, ih, ic: (ib, ic, 0))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, sc_spec, sc_spec, bn_spec, bn_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, adt[..., None], dt[..., None], b_p, c_p)
