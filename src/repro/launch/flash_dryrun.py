import os

if __name__ == "__main__":  # device count must be locked before jax init
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ.get("FLASH_DRYRUN_DEVICES", "256")
        + " "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()

"""Flash-under-sharding dry-run (DESIGN.md §17; ROADMAP PR-3 follow-on).

The kernel route was only ever exercised single-device (interpret on CPU,
compiled single-chip on TPU).  This cell validates ``pallas_call`` under the
production mesh: the flash forward + grads wrapped in ``shard_map`` over the
batch (data-parallel) axes, lowered and compiled against abstract inputs —
no allocation — for both grid variants.  The pruned variant builds its
liveness tables INSIDE the sharded region from the local segment shard, so
the scalar-prefetch indices are per-shard local (exactly what a real
multi-host run needs: no global table gather).

As a module (``python -m repro.launch.flash_dryrun``) it forces the
production device count (override with FLASH_DRYRUN_DEVICES) and writes
``artifacts/dryrun/flash_sharded.json``; ``validate_flash_sharded`` is the
in-process entry benchmarks and tests call against any mesh.
"""

import argparse
import json
import pathlib
import time
import traceback

ARTIFACT_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _sharded_flash_fn(mesh, grid: str, *, causal=True, block_q=128, block_kv=128):
    """shard_map'd loss+grads over the flash route, batch sharded on DP."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ops import flash_attention
    from repro.launch.mesh import dp_axes

    dp = dp_axes(mesh)
    batch_spec = P(dp)

    def local_loss(q, k, v, seg):
        # Liveness tables (grid="pruned") are built inside this body from
        # the *local* segment shard — per-shard scalar prefetch, no global
        # index exchange.
        out = flash_attention(q, k, v, seg, causal, block_q, block_kv, grid)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def local_body(q, k, v, seg):
        loss, grads = jax.value_and_grad(local_loss, argnums=(0, 1, 2))(
            q, k, v, seg
        )
        return loss[None], grads  # rank-1 per-shard loss, concat over DP

    def sharded(q, k, v, seg):
        loss, grads = jax.shard_map(
            local_body,
            mesh=mesh,
            in_specs=(batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(batch_spec, (batch_spec, batch_spec, batch_spec)),
            check_vma=False,
        )(q, k, v, seg)
        # Per-shard partial losses; summing them is the global objective.
        return jnp.sum(loss), grads

    return jax.jit(sharded)


def validate_flash_sharded(
    mesh,
    grid: str,
    *,
    rows_per_shard: int = 2,
    seq: int = 512,
    heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 64,
    block_q: int = 128,
    block_kv: int = 128,
    compile_only: bool = True,
) -> dict:
    """Lower + compile (optionally execute) the sharded flash cell.

    ``rows_per_shard`` scales the global batch to ``dp_size(mesh)`` so the
    batch axis always divides the DP extent.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import dp_size

    dp = dp_size(mesh)
    b = rows_per_shard * dp
    record = {
        "grid": grid,
        "mesh": dict(mesh.shape),
        "batch": b,
        "seq": seq,
        "heads": heads,
        "kv_heads": kv_heads,
        "head_dim": head_dim,
        "compile_only": compile_only,
    }
    t0 = time.perf_counter()
    try:
        fn = _sharded_flash_fn(
            mesh, grid, block_q=block_q, block_kv=block_kv
        )
        f32 = jnp.float32
        abstract = (
            jax.ShapeDtypeStruct((b, seq, heads, head_dim), f32),
            jax.ShapeDtypeStruct((b, seq, kv_heads, head_dim), f32),
            jax.ShapeDtypeStruct((b, seq, kv_heads, head_dim), f32),
            jax.ShapeDtypeStruct((b, seq), jnp.int32),
        )
        compiled = fn.lower(*abstract).compile()
        record["compile_s"] = round(time.perf_counter() - t0, 3)
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                record["argument_bytes"] = int(
                    getattr(mem, "argument_size_in_bytes", 0)
                )
                record["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", 0))
        except Exception:
            pass
        record["status"] = "ok"
    except Exception as exc:  # surfaced in the bench rail / CI assert
        record["status"] = "error"
        record["error"] = f"{type(exc).__name__}: {exc}"
        record["traceback"] = traceback.format_exc(limit=12)
    return record


def main() -> None:
    import jax

    from repro.launch.mesh import make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--mesh", default="single", choices=("single", "multi"),
        help="production mesh: single-pod 16x16 or two-pod 2x16x16 "
             "(needs FLASH_DRYRUN_DEVICES=512)",
    )
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--rows-per-shard", type=int, default=2)
    ap.add_argument("--json", action="store_true", help="print the record JSON")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    records = {}
    for grid in ("dense", "pruned"):
        rec = validate_flash_sharded(
            mesh, grid, rows_per_shard=args.rows_per_shard, seq=args.seq
        )
        records[grid] = rec
        if not args.json:
            print(
                f"[flash-dryrun] grid={grid} mesh={args.mesh} "
                f"chips={mesh.devices.size} status={rec['status']} "
                f"compile={rec.get('compile_s', float('nan'))}s"
            )
            if rec["status"] != "ok":
                print(rec.get("traceback", rec.get("error", "")))

    out = {
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "cells": records,
    }
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    path = ARTIFACT_DIR / "flash_sharded.json"
    path.write_text(json.dumps(out, indent=1))
    if args.json:
        print(json.dumps(out))
    else:
        print(f"[flash-dryrun] artifact: {path}")
    if any(r["status"] != "ok" for r in records.values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
