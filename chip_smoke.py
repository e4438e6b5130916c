"""On-chip smoke test: the ODB trainer at Qwen3-0.6B full width on one TPU.

    python chip_smoke.py

Drives the normal entry points in one process (a TPU belongs to one process
at a time), in this order:

  (a) device check — exits non-zero unless JAX's first device is a TPU;
  (b) kernel parity — compiled flash forward and dQ/dK/dV on one packed
      bf16 input at Qwen3-0.6B attention width, dense and pruned grids,
      against the float32 reference (kernels/ref.py) under "highest"
      matmul precision; pruned must equal dense exactly;
  (c) packed training — ``repro.launch.train.main`` at the published width
      (28 layers, d_model 1024, vocab 151936), flash attention on the
      pruned grid, a few steps with finite losses and a closed epoch audit;
  (d) dense-layout training (XLA attention route), then the packed run
      again with two spawned realization workers, whose losses must be
      bit-identical to (c);
  (e) the last line: one JSON object naming the device.

Every number goes on an earlier line; any failed check raises, and the
script exits non-zero without the JSON line.  Module level imports nothing
but the standard library, so the spawned workers of (d), which re-import
this file, never touch the chip.
"""

from __future__ import annotations

import json
import math
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent

# llava length clone: every sample (<= 1,260 tokens) fits one rank's 2,048
# token budget, so no packed step of this epoch exceeds 2 x 2,048 device
# tokens; the train step at that size compiles to ~10.9 GiB on a v5e.
TRAIN_ARGS = [
    "--arch", "qwen3_0_6b", "--dataset", "llava", "--data-scale", "0.003",
    "--world", "2", "--l-max", "2048", "--buffer", "32", "--prefetch", "8",
    "--steps", "6", "--log-every", "1",
    "--attn-impl", "auto", "--attn-grid", "auto",
]
# Kernel parity input: Qwen3-0.6B attention at one 4k packed row.
PARITY_SHAPE = dict(b=1, s=4096, h=16, kv=8, d=128)
PARITY_SEGMENTS = (700, 1300, 300, 1100, 400)  # then a 296-token padding tail
PARITY_TOL = 2e-2  # max |kernel - ref| / max |ref|, bf16 inputs and outputs


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def device_check():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's first device is "
            f"{dev.platform!r}); this smoke test never runs on the CPU"
        )
    _log(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    return dev, len(devices)


def kernel_parity() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import segment_flash_attention_ref

    b, s, h, kv, d = (PARITY_SHAPE[k] for k in ("b", "s", "h", "kv", "d"))
    seg = np.zeros((b, s), np.int32)
    start = 0
    for i, n in enumerate(PARITY_SEGMENTS, start=1):
        seg[:, start:start + n] = i
        start += n
    seg = jnp.asarray(seg)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, s, kv, d), jnp.bfloat16)
    # Padding rows have no softmax mass: the kernel writes 0 there, the
    # materializing reference a uniform average, so both sides compare on
    # real rows only (the cotangent is zero on padding).
    valid = (seg > 0)[:, :, None, None]
    g = jax.random.normal(keys[3], (b, s, h, d), jnp.bfloat16) * valid

    def kernel_run(grid):
        fn = lambda q_, k_, v_: flash_attention(q_, k_, v_, seg, grid=grid)
        out, vjp = jax.vjp(fn, q, k, v)
        return jax.block_until_ready((out, *vjp(g)))

    results = {}
    for grid in ("dense", "pruned"):
        t0 = time.perf_counter()
        results[grid] = [np.asarray(x) for x in kernel_run(grid)]
        _log(f"kernel grid={grid} fwd+bwd compiled and ran in "
             f"{time.perf_counter() - t0:.1f}s")

    with jax.default_matmul_precision("highest"):
        f32 = lambda x: x.astype(jnp.float32)
        ref_fn = lambda q_, k_, v_: segment_flash_attention_ref(q_, k_, v_, seg)
        out, vjp = jax.vjp(ref_fn, f32(q), f32(k), f32(v))
        ref = [np.asarray(x) for x in jax.block_until_ready((out, *vjp(f32(g))))]
    mask = np.asarray(valid)
    names = ("out", "dq", "dk", "dv")
    for grid, got in results.items():
        for name, a, r in zip(names, got, ref):
            a = a.astype(np.float32)
            if name == "out":
                a, r = a * mask, r * mask
            err = float(np.max(np.abs(a - r)))
            scale = float(np.max(np.abs(r)))
            _check(math.isfinite(err), f"{grid} {name} is finite")
            _log(f"parity grid={grid} {name}: max_abs_err={err!r} "
                 f"ref_max_abs={scale!r} rel={err / scale!r} tol={PARITY_TOL}")
            _check(err <= PARITY_TOL * scale, f"{grid} {name} within tolerance")
    for name, a, p in zip(names, results["dense"], results["pruned"]):
        _check(np.array_equal(a, p), f"pruned {name} equals dense bit for bit")
    _log("parity pruned == dense bit-exact for out, dq, dk, dv")


def train(label: str, extra: list[str], device):
    from repro import obs
    from repro.launch.train import main as train_main

    compiles = obs.counter("train_compile_events_total")
    before = compiles.value
    t0 = time.perf_counter()
    trainer = train_main(TRAIN_ARGS + extra)
    wall = time.perf_counter() - t0
    losses = [rec["loss"] for rec in trainer.history]
    audit = trainer.loader.last_audit
    peak = device.memory_stats().get("peak_bytes_in_use")
    _log(f"{label}: layout={trainer.loader.layout.name} "
         f"attn_impl={trainer.attn_impl} attn_grid={trainer.attn_grid} "
         f"steps={len(losses)} losses={losses!r}")
    _log(f"{label}: eta_identity={audit.eta_identity!r} "
         f"train_compile_events_total={compiles.value - before:g} "
         f"process_peak_bytes_in_use={peak} wall_s={wall:.1f}")
    _check(len(losses) == 6, f"{label} logged 6 steps")
    _check(all(math.isfinite(x) for x in losses), f"{label} losses finite")
    _check(audit.eta_identity == 0.0, f"{label} eta_identity == 0.0")
    return trainer, losses


def main() -> None:
    src = REPO / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import configure_compile_cache

    _log(f"compile cache: {configure_compile_cache()}")
    t0 = time.perf_counter()
    device, count = device_check()

    t = time.perf_counter()
    kernel_parity()
    _log(f"phase kernel_parity wall_s={time.perf_counter() - t:.1f}")

    t = time.perf_counter()
    trainer, packed = train("packed", ["--layout", "packed"], device)
    _check(
        (trainer.attn_impl, trainer.attn_grid) == ("flash", "pruned"),
        "packed layout resolved to attn_impl=flash attn_grid=pruned",
    )
    _log(f"phase train_packed wall_s={time.perf_counter() - t:.1f}")

    t = time.perf_counter()
    trainer, _ = train("dense", ["--layout", "dense"], device)
    _check(trainer.attn_impl == "xla", "dense layout resolved to attn_impl=xla")
    _log(f"phase train_dense wall_s={time.perf_counter() - t:.1f}")

    t = time.perf_counter()
    _, workers = train(
        "packed_workers", ["--layout", "packed", "--num-workers", "2"], device
    )
    _check(workers == packed, "--num-workers 2 losses bit-identical to packed")
    _log("workers: losses bit-identical to the in-process packed run")
    _log(f"phase train_workers wall_s={time.perf_counter() - t:.1f}")
    _log(f"total wall_s={time.perf_counter() - t0:.1f}")

    print(json.dumps({
        "ok": True,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": count},
    }))


if __name__ == "__main__":
    main()
