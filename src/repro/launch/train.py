"""Fault-tolerant training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b \
        --dataset ultrachat --steps 50 --smoke

Wraps the ODB trainer in a resume loop: any crash (preemption, node loss)
restarts from the latest atomic checkpoint; the loader is stateless across
restarts (epoch-seeded), and elastic topology changes re-shard on restore
(train/checkpoint.py).  Straggler mitigation is inherent to the DGAP
alignment (slow ranks lower T_grp via S_min+/C_min+ instead of stalling the
step — see tests/test_protocol.py::test_straggler_liveness).
"""

from __future__ import annotations

import argparse
import dataclasses
import pathlib

import jax

from repro.configs import get_config, get_smoke_config
from repro.core import BucketSpec, OdbConfig
from repro.data import OnlineDynamicLoader, get_dataset
from repro.launch.compile_cache import configure_compile_cache
from repro.models import LM
from repro.stream import EpochAborted
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def _calibrate_layout(
    dataset, world: int, config: OdbConfig, steps: int, bucket_spec: BucketSpec
) -> str:
    """--layout auto: measured dense-vs-packed probe (benchmarks/layout.py)."""
    try:
        from benchmarks.layout import calibrate_layout
    except ImportError:  # benchmarks namespace lives at the repo root
        import pathlib
        import sys

        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
        from benchmarks.layout import calibrate_layout

    cal = calibrate_layout(
        dataset, world, config, steps=steps, bucket_spec=bucket_spec
    )
    rows = cal["results"]
    for name, r in rows.items():
        print(
            f"[train] calibrate {name}: {r['steps_per_s']:.2f} steps/s  "
            f"dev-pad {100 * r['device_padding_fraction']:.2f}%"
        )
    print(f"[train] layout auto -> {cal['layout']}")
    return cal["layout"]


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument(
        "--smoke", action="store_true",
        help="the architecture's reduced-width smoke config (quick runs and "
             "CPU tests); without it the published full-width config",
    )
    ap.add_argument("--dataset", default="ultrachat")
    ap.add_argument("--data-scale", type=float, default=0.002)
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument(
        "--log-every", type=int, default=5,
        help="record (and print at exit) the loss line every N steps",
    )
    ap.add_argument("--l-max", type=int, default=4096)
    ap.add_argument("--buffer", type=int, default=256)
    ap.add_argument("--prefetch", type=int, default=64)
    ap.add_argument("--non-join", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument(
        "--round-deadline", type=float, default=None, metavar="SECONDS",
        help="per-round collective delivery deadline (DESIGN.md §15); a "
             "round whose gather misses it is retried with exponential "
             "backoff, and an exhausted retry budget aborts the epoch into "
             "a resumable checkpoint instead of hanging. Default: off",
    )
    ap.add_argument(
        "--round-retries", type=int, default=2,
        help="gather retries before a missed --round-deadline aborts",
    )
    ap.add_argument(
        "--max-quarantine", type=int, default=0,
        help="per-epoch budget of samples whose online realization may fail "
             "and be quarantined (accounted component X, DESIGN.md §15) "
             "instead of crashing the epoch. Default 0 = strict",
    )
    ap.add_argument(
        "--eager", action="store_true",
        help="offline data path (full-epoch length realization) instead of "
             "the default streaming executor",
    )
    ap.add_argument(
        "--lookahead", type=int, default=None,
        help="admission-window bound on realized lengths in flight "
             "(default: full view multiset, reproducing the eager schedule)",
    )
    ap.add_argument("--no-prefetch", action="store_true")
    ap.add_argument("--prefetch-depth", type=int, default=2)
    ap.add_argument(
        "--layout", default="dense", choices=("dense", "packed", "auto"),
        help="batch layout: dense bucket padding, packed segment streams "
             "(DESIGN.md §10), or auto — a short measured calibration probe "
             "picks the faster layout for this dataset profile",
    )
    ap.add_argument(
        "--calibration-steps", type=int, default=6,
        help="measured steps per layout for --layout auto",
    )
    ap.add_argument(
        "--attn-impl", default="auto", choices=("auto", "xla", "flash"),
        help="training attention route (DESIGN.md §11): XLA blockwise, the "
             "Pallas flash kernel, or auto (flash when packed on TPU)",
    )
    ap.add_argument(
        "--attn-grid", default="auto", choices=("auto", "dense", "pruned"),
        help="flash grid variant (DESIGN.md §17): dense walks every kv tile, "
             "pruned skips dead-tile DMA through the scalar-prefetch "
             "liveness index; auto = pruned when packed on TPU",
    )
    ap.add_argument(
        "--attn-autotune", action="store_true",
        help="pick the flash kernel's (block_q, block_kv) per shape cell "
             "from a short measured probe (cached under artifacts/autotune/)",
    )
    ap.add_argument(
        "--device-put", action="store_true",
        help="stage jax.device_put on the prefetch producer so H2D hides "
             "under the jitted step",
    )
    ap.add_argument(
        "--num-workers", type=int, default=0,
        help="spawned realization worker processes staging steps through a "
             "shared-memory ring (DESIGN.md §14); 0 = in-process path. The "
             "delivered step stream is bit-identical either way",
    )
    ap.add_argument(
        "--telemetry", default=None, metavar="DIR",
        help="enable the obs subsystem and write metrics.json / trace.json / "
             "rounds.json into DIR at exit (DESIGN.md §13)",
    )
    ap.add_argument(
        "--telemetry-port", type=int, default=None, metavar="PORT",
        help="serve a live Prometheus scrape endpoint (GET /metrics) from a "
             "daemon thread on this port while training (0 = ephemeral); "
             "independent of --telemetry's at-exit files",
    )
    ap.add_argument(
        "--hosts", type=int, default=1,
        help="simulated multi-host lane (DESIGN.md §16): partition the DGAP "
             "ranks over this many sharded admission windows, each running "
             "its own cursor over its rank block. Run under "
             "XLA_FLAGS=--xla_force_host_platform_device_count=N to give "
             "each simulated host its own device block; must divide --world",
    )
    return ap.parse_args(argv)


def build_loader(args: argparse.Namespace, vocab_size: int) -> OnlineDynamicLoader:
    """The ODB loader these arguments describe (``--layout auto`` runs the
    calibration probe first)."""
    dataset = get_dataset(args.dataset, scale=args.data_scale)
    odb_cfg = OdbConfig(
        l_max=args.l_max, buffer_size=args.buffer,
        prefetch_factor=args.prefetch, num_workers=4,
        join_mode=not args.non_join,
        round_deadline_s=args.round_deadline,
        round_retries=args.round_retries,
        max_quarantine=args.max_quarantine,
    )
    bucket_spec = BucketSpec(min_len=128, max_len=16384, max_count=1024)
    layout = args.layout
    if layout == "auto":
        layout = _calibrate_layout(
            dataset, args.world, odb_cfg, args.calibration_steps, bucket_spec
        )
    return OnlineDynamicLoader(
        dataset,
        world_size=args.world,
        config=odb_cfg,
        bucket_spec=bucket_spec,
        layout=layout,
        vocab_size=vocab_size,
        num_hosts=args.hosts,
    )


def main(argv: list[str] | None = None) -> Trainer:
    """Parse ``argv`` (default: the command line), train, and return the
    finished :class:`Trainer` (its ``history``, routes and ``loader``)."""
    configure_compile_cache()
    args = parse_args(argv)

    reporter = None
    if args.telemetry:
        from repro import obs

        # Before any instrumented object is built, so construction-time
        # cached instruments bind to live metrics.
        reporter = obs.enable_telemetry(args.telemetry)
    scrape = None
    if args.telemetry_port is not None:
        from repro import obs

        scrape = obs.start_scrape_server(args.telemetry_port)
        print(f"[train] telemetry scrape: {scrape.url}")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(
        cfg, attn_impl=args.attn_impl, attn_grid=args.attn_grid,
        attn_autotune=args.attn_autotune,
    )
    model = LM(cfg)
    loader = build_loader(args, cfg.vocab_size)
    layout = loader.layout.name
    trainer = Trainer(
        model, loader,
        OptimizerConfig(total_steps=max(args.steps, 100)),
        TrainerConfig(
            checkpoint_dir=args.checkpoint_dir, checkpoint_every=20,
            log_every=args.log_every, max_steps=args.steps,
            streaming=not args.eager, prefetch=not args.no_prefetch,
            prefetch_depth=args.prefetch_depth, lookahead=args.lookahead,
            device_put=args.device_put, num_workers=args.num_workers,
        ),
    )

    restarts = 0
    while True:
        try:
            state, step = trainer.restore_or_init(jax.random.PRNGKey(0))
            epoch = 0
            while step < args.steps:
                state, step = trainer.train_epoch(state, epoch=epoch, start_step=step)
                epoch += 1
            break
        except KeyboardInterrupt:
            raise
        except EpochAborted as exc:  # degraded-mode closure (DESIGN.md §15.4)
            restarts += 1
            print(
                f"[train] epoch aborted ({exc.cause}); "
                f"restart {restarts}/{args.max_restarts}"
            )
            if exc.failed_ranks:
                # Full casualty list, not just the first straggler — a
                # multi-rank stall usually means a shared link, not a node.
                print(f"[train] failed ranks: {exc.failed_ranks}")
            if args.checkpoint_dir:
                # The abort carries a valid stream checkpoint; persist it
                # beside the model checkpoints so an operator (or the next
                # restart of a stream-resuming driver) can continue the
                # identical step sequence instead of replaying the epoch.
                abort_path = pathlib.Path(args.checkpoint_dir) / "stream_abort.json"
                exc.checkpoint().save(str(abort_path))
                print(f"[train] abort stream checkpoint: {abort_path}")
            if restarts > args.max_restarts or not args.checkpoint_dir:
                raise
        except Exception as exc:  # crash -> resume from latest checkpoint
            restarts += 1
            print(f"[train] crash ({type(exc).__name__}: {exc}); restart {restarts}")
            if restarts > args.max_restarts or not args.checkpoint_dir:
                raise

    print(
        f"[train] layout={layout} attn_impl={trainer.attn_impl} "
        f"attn_grid={trainer.attn_grid}"
    )
    for h in trainer.history[-10:]:
        print(Trainer.format_log_line(h))
    audit = loader.last_audit
    if audit:
        print(f"eta_identity={audit.eta_identity} eta_quota={audit.eta_quota}")
    if loader.last_prefetch_stats is not None:
        st = loader.last_prefetch_stats
        print(f"prefetch hit_rate={st.hit_rate:.2f} waits={st.wait_s:.3f}s")
    if loader.last_worker_stats is not None:
        ws = loader.last_worker_stats
        print(
            f"workers completed={ws.completed} shm={ws.shm_results} "
            f"inline={ws.inline_results} reexec={ws.reexecuted} "
            f"failures={ws.worker_failures} wait={ws.wait_s:.3f}s"
        )
    if reporter is not None:
        executor = loader.last_executor
        paths = reporter.write(
            round_audit=None if executor is None else executor.telemetry,
            extra={
                "arch": cfg.name,
                "layout": layout,
                "attn_impl": trainer.attn_impl,
                "steps": step,
            },
        )
        for kind, path in sorted(paths.items()):
            print(f"[train] telemetry {kind}: {path}")
    if scrape is not None:
        scrape.stop()
    return trainer


if __name__ == "__main__":
    main()
