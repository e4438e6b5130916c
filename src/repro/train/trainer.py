"""ODB-integrated trainer (paper §2.4 metadata contract + Eq. 2 scaling).

Two execution paths:

  * ``Trainer`` — the deployment path: consumes step-aligned per-rank
    ``DeviceBatch``es from :class:`repro.data.loader.OnlineDynamicLoader`
    (whatever batch layout the loader was built with — DESIGN.md §10),
    unifies them into one global SPMD batch, and drives the jitted
    ``train_step`` shared with launch/steps.py.  The global masked per-token
    mean that the step computes is exactly the token-level scaled objective:
    IDLE ranks contribute zero tokens and are annihilated (Eq. 2 with
    t_r = 0).  Fault tolerance: periodic atomic checkpoints +
    resume-from-latest.

  * ``dp_shardmap_step`` — the paper-literal path: per-rank mean losses
    prescaled by ``W·w_r`` and mean-reduced over an explicit ``psum``,
    with optional bf16 gradient compression + error feedback.  This is the
    vehicle for the Eq. 2 bit-exactness tests and the loss-scaling-mode
    benchmark (Table 18).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.layout import (
    BatchLayout,
    global_batch_arrays,
    unify_step_shapes,
)
from repro.core.loss_scaling import prescale_factor
from repro.data.loader import LoaderStep, OnlineDynamicLoader
from repro.models.model import LM, shift_labels
from repro.train import checkpoint as ckpt
from repro.train.compression import init_error_state, psum_compressed
from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state

__all__ = [
    "Trainer",
    "TrainerConfig",
    "assemble_model_batch",
    "dp_shardmap_step",
    "global_batch_arrays",  # re-exported from core.layout (layout-aware)
    "make_train_step",
    "resolve_attn_grid",
    "resolve_attn_impl",
    "unify_step_shapes",
]


def resolve_attn_impl(cfg, *, packed: bool, backend: str | None = None) -> str:
    """Pin ``attn_impl="auto"`` to a concrete route for one training run.

    The routing matrix (DESIGN.md §11): the Pallas flash kernel exactly when
    the layout packs segments into rows (where its segment-range block
    skipping pays), the attention layout is GQA, and the backend compiles
    Pallas (TPU) — the XLA blockwise path otherwise.  CPU runs keep XLA by
    default (interpret-mode Pallas is a test/bench vehicle, not a train
    path); an explicit ``attn_impl="flash"`` is honored unchanged.

    Resolving at trainer-build time (instead of leaving "auto" to trace
    time) makes the compiled route a recorded property of the run.
    """
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    if cfg.attn_kind != "gqa":
        return "xla"
    backend = backend or jax.default_backend()
    return "flash" if (packed and backend == "tpu") else "xla"


def resolve_attn_grid(cfg, *, packed: bool, backend: str | None = None) -> str:
    """Pin ``attn_grid="auto"`` to a concrete flash grid variant (DESIGN.md
    §17): the scalar-prefetch pruned grid exactly when the layout packs
    segments into rows (the liveness tables are built from segment ids) and
    the backend compiles Pallas; dense otherwise.  An explicit "pruned" is
    honored whenever segments exist — interpret mode included, which is how
    CPU tests and benches exercise the path."""
    grid = getattr(cfg, "attn_grid", "auto")
    if not packed:
        return "dense"  # no segments -> nothing to build liveness from
    if grid != "auto":
        return grid
    backend = backend or jax.default_backend()
    return "pruned" if backend == "tpu" else "dense"


def make_train_step(model: LM, opt_cfg: OptimizerConfig):
    """(state, batch) -> (state, metrics) — THE train step.

    One builder shared by the deployment trainer (jitted shape-polymorphic
    over the bucket grids) and the launch/dry-run compile cells
    (``launch/steps.py`` pins shapes + mesh shardings around this same
    function), so what the dry-run lowers is what training runs.

    Loss normalization: the global masked per-token mean — identical to the
    paper's exact token-level scaled objective (Eq. 2 collapses to the global
    per-token mean in SPMD; bit-exactness of the per-rank weighting form is
    verified separately in tests/test_loss_scaling.py).
    """

    def train_step(state, batch):
        # Executes at trace time only, so the counter is a compile-event
        # census: one tick per (shape, layout) specialization XLA builds.
        obs.counter(
            "train_compile_events_total", help="train_step trace/compile events"
        ).inc()
        obs.instant("train/compile", cat="train")

        def loss_fn(params):
            loss_sum, tokens = model.loss_sums(params, batch)
            return loss_sum / jnp.maximum(tokens, 1.0), tokens

        (loss, tokens), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"]
        )
        new_params, new_opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], opt_cfg
        )
        metrics = {"loss": loss, "tokens": tokens, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


@contextlib.contextmanager
def _timed_phase(span_name: str, metric: str, help: str):
    """Run one step phase under a span (ring and profiler) and add its host
    time to a cumulative seconds counter."""
    with obs.span(span_name, cat="train"):
        t0 = time.perf_counter()
        yield
        obs.counter(metric, help=help, unit="seconds").inc(time.perf_counter() - t0)


def assemble_model_batch(loader_step: LoaderStep, layout: BatchLayout) -> dict:
    """Turn one aligned LoaderStep into the jitted-step batch dict.

    Uses the device-resident arrays staged by the prefetch producer when
    present (device-put overlap), otherwise assembles from host numpy.  The
    packed layout threads positions/segments through to the model (segment-
    aware attention masking + segment-aware label shift); the dense layout
    keeps the lean three-array contract — one sample per row under causal
    masking realizes the identical objective without the segment compare.
    """
    arrays = loader_step.device
    if arrays is None:
        with _timed_phase(
            "train/pad", "train_pad_seconds_total",
            "host-side batch padding/assembly time",
        ):
            host = global_batch_arrays(loader_step.batches, layout)
        with _timed_phase(
            "train/device_put", "train_device_put_seconds_total",
            "host-to-device transfer dispatch time",
        ):
            arrays = {k: jnp.asarray(v) for k, v in host.items()}
    tokens = arrays["tokens"]
    if layout.needs_segments:
        segments = arrays["segments"]
        labels, mask = shift_labels(tokens, arrays["loss_mask"], segments=segments)
        return {
            "tokens": tokens,
            "positions": arrays["positions"],
            "segments": segments,
            "labels": labels,
            "loss_mask": mask,
        }
    labels, mask = shift_labels(tokens, arrays["loss_mask"])
    return {"tokens": tokens, "labels": labels, "loss_mask": mask}


@dataclasses.dataclass
class TrainerConfig:
    checkpoint_dir: str | None = None
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    log_every: int = 10
    max_steps: int | None = None
    # Data path selection (DESIGN.md §9): the streaming executor admits views
    # through a bounded-lookahead window and overlaps data-side work with the
    # jitted step via a background prefetcher; eager is the offline reference.
    streaming: bool = True
    prefetch: bool = True
    prefetch_depth: int = 2
    lookahead: int | None = None
    # Stage jax.device_put on the prefetch producer so H2D transfer hides
    # under the jitted step (ROADMAP "device-put overlap").
    device_put: bool = False
    # Multi-process realization workers (DESIGN.md §14): 0 keeps layout
    # realization in-process; > 0 spawns that many worker processes staging
    # steps through a shared-memory ring (bit-identical step stream).
    num_workers: int = 0


class Trainer:
    """End-to-end ODB training driver (single-process; mesh-agnostic)."""

    def __init__(
        self,
        model: LM,
        loader: OnlineDynamicLoader,
        opt_cfg: OptimizerConfig | None = None,
        cfg: TrainerConfig | None = None,
        mesh=None,
    ):
        self.model = model
        self.loader = loader
        self.opt_cfg = opt_cfg or OptimizerConfig()
        self.cfg = cfg or TrainerConfig()
        self.mesh = mesh
        self._train_step = None
        self.history: list[dict] = []
        self.attn_impl: str | None = None  # resolved at _build_step
        self.attn_grid: str | None = None  # resolved at _build_step

    def _build_step(self):
        # Pin the "auto" kernel route against the loader's actual layout so
        # what this trainer jits is explicit (and loggable), not an implicit
        # function of the backend probed mid-trace.
        packed = self.loader.layout.needs_segments
        self.attn_impl = resolve_attn_impl(self.model.cfg, packed=packed)
        self.attn_grid = resolve_attn_grid(self.model.cfg, packed=packed)
        pins = {}
        if self.attn_impl != self.model.cfg.attn_impl:
            pins["attn_impl"] = self.attn_impl
        if self.attn_grid != self.model.cfg.attn_grid:
            pins["attn_grid"] = self.attn_grid
        if pins:
            self.model = dataclasses.replace(
                self.model,
                cfg=dataclasses.replace(self.model.cfg, **pins),
            )
        self._train_step = jax.jit(
            make_train_step(self.model, self.opt_cfg), donate_argnums=(0,)
        )

    def init_state(self, rng) -> dict:
        params = self.model.init(rng)
        return {"params": params, "opt": init_opt_state(params, self.opt_cfg)}

    def restore_or_init(self, rng) -> tuple[dict, int]:
        if self.cfg.checkpoint_dir and ckpt.latest_step(self.cfg.checkpoint_dir) is not None:
            like = jax.eval_shape(self.init_state, rng)
            state, step = ckpt.restore_checkpoint(self.cfg.checkpoint_dir, like)
            return state, step
        return self.init_state(rng), 0

    def _epoch_steps(self, epoch: int):
        """Pick the data path: streaming (default, overlapped) or eager."""
        if self.cfg.streaming:
            return self.loader.streaming_epoch(
                epoch,
                lookahead=self.cfg.lookahead,
                prefetch=self.cfg.prefetch,
                prefetch_depth=self.cfg.prefetch_depth,
                device_put=self.cfg.device_put,
                num_workers=self.cfg.num_workers,
            )
        return self.loader.epoch(epoch, device_put=self.cfg.device_put)

    def train_epoch(self, state: dict, epoch: int = 0, start_step: int = 0):
        """Run one epoch from ``start_step``; returns (state, step index).

        Each step runs under the span ``train/step`` with the phases
        ``train/realize`` (the data path), ``train/assemble`` (the batch
        dict; ``train/pad`` and ``train/device_put`` inside it when the host
        assembles), ``train/dispatch`` (enqueueing the jitted step) and, every
        ``log_every`` steps, ``train/log`` (the record's read of the loss,
        which waits for the device).  The last ``train/step`` of an epoch
        holds only the realize that found the epoch's end.
        """
        if self._train_step is None:
            self._build_step()
        step_idx = start_step
        emitted = 0
        tokens_seen = 0
        last_log = None  # (time, emitted, tokens) at the previous record's sync
        m_steps = obs.counter("train_steps_total", help="optimizer steps run")
        m_tokens = obs.counter("train_tokens_total", help="real tokens trained on")
        m_step_dur = obs.histogram(
            "train_step_duration_seconds",
            help="host time of one train step (realize+assemble+dispatch)",
            unit="seconds",
        )
        step_iter = iter(self._epoch_steps(epoch))
        while True:
            with obs.span("train/step", cat="train", step=step_idx + 1):
                step_t0 = time.perf_counter()
                # Realize: pull the next aligned step out of the data path
                # (admission + protocol rounds + layout, or a prefetch dequeue).
                with _timed_phase(
                    "train/realize", "train_realize_seconds_total",
                    "data-path time to the next aligned step",
                ):
                    loader_step = next(step_iter, None)
                if loader_step is None:
                    break
                with obs.span("train/assemble", cat="train"):
                    batch = assemble_model_batch(loader_step, self.loader.layout)
                # The host's enqueue only: the device's time for the step is
                # in the profiler trace, and waiting here would change the
                # schedule being measured.
                with _timed_phase(
                    "train/dispatch", "train_dispatch_seconds_total",
                    "host time to dispatch the jitted train_step",
                ):
                    state, metrics = self._train_step(state, batch)
                step_idx += 1
                emitted += loader_step.metadata.emitted_samples
                tokens_seen += loader_step.metadata.total_tokens
                m_steps.inc()
                m_tokens.inc(loader_step.metadata.total_tokens)
                m_step_dur.observe(time.perf_counter() - step_t0)
                if step_idx % self.cfg.log_every == 0:
                    with obs.span("train/log", cat="train"):
                        rec, last_log = self._publish_log_record(
                            metrics, loader_step, step_idx, emitted, tokens_seen,
                            last_log,
                        )
                    self.history.append(rec)
                if (
                    self.cfg.checkpoint_dir
                    and step_idx % self.cfg.checkpoint_every == 0
                ):
                    ckpt.save_checkpoint(
                        self.cfg.checkpoint_dir, step_idx, state,
                        keep=self.cfg.keep_checkpoints,
                    )
            if self.cfg.max_steps and step_idx >= self.cfg.max_steps:
                break
        return state, step_idx

    def _publish_log_record(
        self, metrics, loader_step, step_idx: int, emitted: int,
        tokens_seen: int, last_log: tuple[float, int, int] | None,
    ) -> tuple[dict, tuple[float, int, int]]:
        """Publish step metrics to the registry; return the log record and
        the (time, emitted, tokens) mark the next record's rates start from.

        Reading the loss waits for this step on the device, so the interval
        from the previous record's mark is wall time of finished steps, with
        no compile once every shape has been seen.  The first record of an
        epoch has no interval: its rates read NaN and the rate gauges keep
        their last value.

        One value set feeds the registry gauges, ``self.history`` and the
        stdout line (:meth:`format_log_line`) — the record is a *view* of the
        same snapshot ``metrics.json`` serializes, not a second bookkeeping
        path.
        """
        loss = float(metrics["loss"])
        now = time.perf_counter()
        values = {
            "train_loss": loss,
            "train_step_tokens": float(metrics["tokens"]),
            "train_grad_norm": float(metrics["grad_norm"]),
            "train_batch_padding": loader_step.metadata.padding_fraction,
            "train_device_padding": (
                1.0 - loader_step.metadata.total_tokens / loader_step.device_tokens
                if loader_step.device_tokens
                else 0.0
            ),
        }
        if last_log is not None and now > last_log[0]:
            dt = now - last_log[0]
            values["train_samples_per_second"] = (emitted - last_log[1]) / dt
            values["train_tokens_per_second"] = (tokens_seen - last_log[2]) / dt
        reg = obs.default_registry()
        for name, value in values.items():
            reg.gauge(name).set(value)
        rec = {
            "step": step_idx,
            "loss": values["train_loss"],
            "tokens": values["train_step_tokens"],
            "grad_norm": values["train_grad_norm"],
            "emitted_samples": emitted,
            "sam_per_s": values.get("train_samples_per_second", math.nan),
            "padding": values["train_batch_padding"],
            "device_padding": values["train_device_padding"],
        }
        return rec, (now, emitted, tokens_seen)

    @staticmethod
    def format_log_line(rec: dict) -> str:
        """Render one history record (the stdout view of the same snapshot)."""
        return (
            f"step {rec['step']:>6}  loss {rec['loss']:.4f}  "
            f"tokens {rec['tokens']:>8.0f}  grad_norm {rec['grad_norm']:.3f}  "
            f"sam/s {rec['sam_per_s']:.1f}  pad {rec['padding']:.3f}  "
            f"dev_pad {rec['device_padding']:.3f}"
        )


# -----------------------------------------------------------------------------
# Paper-literal shard_map DP step (Eq. 2 prescaling + optional compression)
# -----------------------------------------------------------------------------


def dp_shardmap_step(
    model: LM,
    mesh,
    opt_cfg: OptimizerConfig,
    *,
    loss_mode: str = "exact_token",
    compress_grads: bool = False,
):
    """Per-rank DDP-style step over the ``data`` axis of ``mesh``.

    Each data shard computes its local mean loss L̄_r, prescales it by
    ``W · w_r`` (Eq. 2), and the psum-mean over shards reproduces the global
    objective; gradients reduce via psum (optionally bf16-compressed with
    error feedback).
    """
    world = mesh.shape["data"]

    def local_loss(params, batch):
        loss_sum, tokens = model.loss_sums(params, batch)
        samples = jnp.sum(jnp.max(batch["loss_mask"], axis=1))
        mean_local = loss_sum / jnp.maximum(tokens, 1.0)
        t_tok = jax.lax.psum(tokens, "data")
        n_tot = jax.lax.psum(samples, "data")
        factor = prescale_factor(
            tokens, jnp.maximum(t_tok, 1.0), world, loss_mode,
            local_samples=samples, global_samples=jnp.maximum(n_tot, 1.0),
        )
        scaled = mean_local * factor
        # DDP post-averaging: mean over ranks == psum / W
        return jax.lax.psum(scaled, "data") / world, tokens

    def step(state, batch, err):
        def lf(params):
            return local_loss(params, batch)

        (loss, tokens), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
        # Local grads hold only this shard's term ∂(scaled_r/W)/∂θ; the DDP
        # AllReduce is the explicit psum below (bf16-compressed if enabled).
        if compress_grads:
            grads, err = psum_compressed(grads, err, "data")
        else:
            grads = jax.lax.psum(grads, "data")
        params, opt, om = adamw_update(state["params"], grads, state["opt"], opt_cfg)
        return {"params": params, "opt": opt}, {"loss": loss, "tokens": tokens, **om}, err

    wrapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P(),  # state replicated across data (DDP semantics)
            {"tokens": P("data", None), "labels": P("data", None), "loss_mask": P("data", None)},
            P(),
        ),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(wrapped, donate_argnums=(0,)), init_error_state
