"""Trainer integration: loss decreases, checkpoints, compression, shardmap DP."""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import BucketSpec, OdbConfig
from repro.data import OnlineDynamicLoader, get_dataset
from repro.data.datasets import DatasetSpec
from repro.data.pipeline import PipelinePolicy, RawRecord
from repro.models import LM
from repro.train import checkpoint as ckpt
from repro.train.optimizer import (
    OptimizerConfig,
    adamw_update,
    cosine_lr,
    global_norm,
    init_opt_state,
)
from repro.train.trainer import Trainer, TrainerConfig, global_batch_arrays


def tiny_dataset(n=96):
    def make(size, seed):
        import random
        rng = random.Random(seed)
        from repro.data.datasets import _records_from_lengths
        return _records_from_lengths([rng.randint(8, 120) for _ in range(size)])
    return DatasetSpec(
        name="tiny", size=n, policy=PipelinePolicy(cutoff_len=256), make_records=make
    )


class TestOptimizer:
    def test_cosine_schedule(self):
        cfg = OptimizerConfig(lr=1e-3, warmup_ratio=0.1, total_steps=100)
        lrs = [float(cosine_lr(jnp.float32(s), cfg)) for s in (0, 5, 10, 50, 100)]
        assert lrs[0] < lrs[1] < lrs[2]  # warmup
        assert lrs[2] >= lrs[3] >= lrs[4]  # decay
        assert lrs[4] >= cfg.lr * cfg.min_lr_fraction * 0.99

    def test_adamw_reduces_quadratic(self):
        cfg = OptimizerConfig(lr=0.1, warmup_ratio=0.0, total_steps=50, weight_decay=0.0)
        params = {"w": jnp.ones((4,)) * 3.0}
        opt = init_opt_state(params, cfg)
        for _ in range(50):
            grads = {"w": 2 * params["w"]}
            params, opt, _ = adamw_update(params, grads, opt, cfg)
        assert float(jnp.abs(params["w"]).max()) < 1.0

    def test_grad_clip(self):
        cfg = OptimizerConfig(grad_clip=1.0)
        params = {"w": jnp.zeros((3,))}
        opt = init_opt_state(params, cfg)
        _, _, metrics = adamw_update(params, {"w": jnp.ones((3,)) * 100}, opt, cfg)
        assert float(metrics["grad_norm"]) > 1.0  # reported pre-clip

    def test_bf16_moments(self):
        cfg = OptimizerConfig(moment_dtype="bfloat16")
        params = {"w": jnp.ones((4,))}
        opt = init_opt_state(params, cfg)
        assert opt["m"]["w"].dtype == jnp.bfloat16


class TestEndToEnd:
    def test_odb_training_loss_decreases(self):
        cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=256)
        model = LM(cfg)
        loader = OnlineDynamicLoader(
            tiny_dataset(), world_size=4,
            config=OdbConfig(l_max=256, buffer_size=16, prefetch_factor=8, num_workers=2),
            bucket_spec=BucketSpec(min_len=32, max_len=256, align=32, max_count=64),
            vocab_size=256,
        )
        trainer = Trainer(
            model, loader,
            OptimizerConfig(lr=3e-3, total_steps=60, warmup_ratio=0.05),
            TrainerConfig(log_every=1),
        )
        state = trainer.init_state(jax.random.PRNGKey(0))
        state, steps = trainer.train_epoch(state, epoch=0)
        state, steps = trainer.train_epoch(state, epoch=1, start_step=steps)
        losses = [h["loss"] for h in trainer.history]
        assert steps >= 4
        assert losses[-1] < losses[0], losses
        audit = loader.last_audit
        assert audit.eta_identity == 0.0  # join-mode coverage held during training

    def test_global_batch_assembly_unifies_shapes(self):
        from repro.core.layout import DeviceBatch

        def db(rows, t):
            return DeviceBatch(
                tokens=np.ones((rows, t), np.int32),
                positions=np.zeros((rows, t), np.int32),
                segments=np.ones((rows, t), np.int32),
                loss_mask=np.ones((rows, t), np.float32),
                lengths=np.full((rows,), t, np.int32),
                real_samples=rows, real_tokens=rows * t,
            )

        out = global_batch_arrays([db(2, 8), db(4, 16)])
        assert out["tokens"].shape == (8, 16)
        assert out["loss_mask"][:2, 8:].sum() == 0  # re-padded region masked
        assert out["segments"][:2, 8:].sum() == 0  # grown region is padding


def _host_lines(logdir) -> dict:
    """Host-thread lines of the newest profiler trace under ``logdir``:
    line name -> [(event name, start ns, end ns)]."""
    from jax.profiler import ProfileData

    path = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))[-1]
    lines = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines[line.name] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                ]
    return lines


def _packed_trainer(log_every: int, max_steps: int) -> Trainer:
    cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=256)
    loader = OnlineDynamicLoader(
        tiny_dataset(), world_size=2,
        config=OdbConfig(l_max=256, buffer_size=16, prefetch_factor=8, num_workers=2),
        layout="packed", vocab_size=256,
    )
    return Trainer(
        LM(cfg), loader, OptimizerConfig(),
        TrainerConfig(log_every=log_every, max_steps=max_steps),
    )


class TestStepSpans:
    """The step loop's spans reach the profiler trace with the ring off."""

    def test_phases_nest_in_train_step_on_the_loop_thread(self, tmp_path):
        trainer = _packed_trainer(log_every=3, max_steps=3)
        state = trainer.init_state(jax.random.PRNGKey(0))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            trainer.train_epoch(state, epoch=0)
        finally:
            jax.profiler.stop_trace()
        lines = _host_lines(tmp_path)
        (loop,) = [n for n, evs in lines.items() if any(e[0] == "train/step" for e in evs)]
        events = lines[loop]
        phases = {"train/realize", "train/assemble", "train/dispatch"}
        whole = [
            (lo, hi) for name, lo, hi in events
            if name == "train/step"
            and phases <= {n for n, s, t in events if lo <= s and t <= hi}
        ]
        assert len(whole) == 3
        assert any(name == "train/log" for name, _, _ in events)
        producers = [n for n, evs in lines.items() if any(e[0] == "prefetch/produce" for e in evs)]
        assert producers and loop not in producers

    def test_log_rates_cover_the_interval_between_records(self):
        trainer = _packed_trainer(log_every=1, max_steps=3)
        trainer.train_epoch(trainer.init_state(jax.random.PRNGKey(0)), epoch=0)
        rates = [h["sam_per_s"] for h in trainer.history]
        assert len(rates) == 3 and np.isnan(rates[0])  # no interval before the first
        assert all(np.isfinite(r) and r > 0 for r in rates[1:]), rates
        from repro import obs

        flat = obs.default_registry().flat()
        assert flat["train_samples_per_second"] == rates[-1]
        assert flat["train_tokens_per_second"] > 0
        assert "train_dispatch_seconds_total" in flat
        assert "train_compute_seconds_total" not in flat

    def test_step_hlo_names_the_model_parts(self):
        """Forward and backward operations carry the part's scope in their
        HLO metadata, which the device trace shows."""
        import re

        from repro.train.trainer import make_train_step

        cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=256)
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": init_opt_state(params, OptimizerConfig())}
        b, s = 2, 64
        batch = {
            "tokens": jnp.zeros((b, s), jnp.int32),
            "labels": jnp.zeros((b, s), jnp.int32),
            "loss_mask": jnp.ones((b, s), jnp.float32),
        }
        step = jax.jit(make_train_step(model, OptimizerConfig()))
        ops = re.findall(r'op_name="([^"]*)"', step.lower(state, batch).compile().as_text())
        for scope in ("attention", "mlp", "lm_loss", "adamw"):
            pat = re.compile(rf"(?:^|[/(;]){scope}(?=[/);]|$)")
            scoped = [o for o in ops if pat.search(o)]
            assert any("transpose(" not in o for o in scoped), scope
            if scope != "adamw":  # the update follows the gradient: no backward
                assert any("transpose(" in o for o in scoped), scope
        assert any("transpose(jvp(lm_loss))" in o for o in ops)


class TestCheckpoint:
    def test_roundtrip_and_rotation(self, tmp_path):
        state = {
            "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "opt": {"step": jnp.array(7, jnp.int32)},
        }
        for s in (1, 2, 3, 4):
            ckpt.save_checkpoint(tmp_path, s, state, keep=2)
        assert ckpt.latest_step(tmp_path) == 4
        assert len(list(pathlib.Path(tmp_path).glob("step_*.npz"))) == 2
        like = jax.tree.map(lambda x: jnp.zeros_like(x), state)
        restored, step = ckpt.restore_checkpoint(tmp_path, like)
        assert step == 4
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
        )

    def test_shape_mismatch_rejected(self, tmp_path):
        state = {"w": jnp.zeros((2, 3))}
        ckpt.save_checkpoint(tmp_path, 1, state)
        with pytest.raises(ValueError):
            ckpt.restore_checkpoint(tmp_path, {"w": jnp.zeros((3, 3))})

    def test_corrupt_latest_falls_back_to_previous(self, tmp_path):
        """DESIGN.md §15.6: a torn latest checkpoint (truncated npz) must be
        detected, warned about, and skipped in favor of the previous keep-k
        checkpoint — never crash the restart loop, never half-apply."""
        like = {"w": jnp.zeros((2, 3)), "step": jnp.zeros((), jnp.int32)}
        for s in (1, 2):
            state = {
                "w": jnp.full((2, 3), float(s)),
                "step": jnp.array(s, jnp.int32),
            }
            ckpt.save_checkpoint(tmp_path, s, state, keep=3)
        latest = pathlib.Path(tmp_path) / "step_00000002.npz"
        data = latest.read_bytes()
        latest.write_bytes(data[: len(data) // 2])  # torn write
        with pytest.warns(RuntimeWarning, match="step_00000002"):
            restored, step = ckpt.restore_checkpoint(tmp_path, like)
        assert step == 1
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.full((2, 3), 1.0)
        )

    def test_corrupt_explicit_step_never_falls_back(self, tmp_path):
        """Asking for a specific step and silently getting a different one
        would be corruption: explicit requests fail hard."""
        for s in (1, 2):
            ckpt.save_checkpoint(tmp_path, s, {"w": jnp.full((2,), float(s))})
        latest = pathlib.Path(tmp_path) / "step_00000002.npz"
        latest.write_bytes(latest.read_bytes()[:10])
        with pytest.raises(Exception):
            ckpt.restore_checkpoint(tmp_path, {"w": jnp.zeros((2,))}, step=2)

    def test_all_checkpoints_corrupt_raises_with_candidates(self, tmp_path):
        ckpt.save_checkpoint(tmp_path, 1, {"w": jnp.zeros((2,))})
        p = pathlib.Path(tmp_path) / "step_00000001.npz"
        p.write_bytes(b"\x00" * 16)
        with pytest.warns(RuntimeWarning):
            with pytest.raises(FileNotFoundError, match="step_00000001"):
                ckpt.restore_checkpoint(tmp_path, {"w": jnp.zeros((2,))})

    def test_trainer_resume(self, tmp_path):
        cfg = dataclasses.replace(get_smoke_config("olmo_1b"), vocab_size=128)
        model = LM(cfg)
        loader = OnlineDynamicLoader(
            tiny_dataset(48), world_size=2,
            config=OdbConfig(l_max=256, buffer_size=8, prefetch_factor=4, num_workers=2),
            bucket_spec=BucketSpec(min_len=32, max_len=256, align=32, max_count=64),
            vocab_size=128,
        )
        tcfg = TrainerConfig(checkpoint_dir=str(tmp_path), checkpoint_every=2, log_every=1)
        trainer = Trainer(model, loader, OptimizerConfig(), tcfg)
        state, start = trainer.restore_or_init(jax.random.PRNGKey(0))
        assert start == 0
        state, steps = trainer.train_epoch(state, 0)
        assert ckpt.latest_step(tmp_path) is not None
        # simulate crash + restart
        trainer2 = Trainer(model, loader, OptimizerConfig(), tcfg)
        state2, start2 = trainer2.restore_or_init(jax.random.PRNGKey(0))
        assert start2 > 0


class TestCompression:
    def test_error_feedback_unbiased_over_steps(self):
        from repro.train.compression import compress_decompress, init_error_state
        g = {"w": jnp.full((256,), 1.0 + 2.0 ** -12)}  # not bf16-representable
        err = init_error_state(g)
        acc = jnp.zeros((256,))
        for _ in range(64):
            gq, err = compress_decompress(g, err)
            acc = acc + gq["w"].astype(jnp.float32)
        mean = acc / 64
        np.testing.assert_allclose(np.asarray(mean), np.asarray(g["w"]), rtol=1e-4)


class TestPackedEmission:
    """First-class packed-segment layout (DESIGN.md §10)."""

    def test_packed_layout_trains_with_segment_masking(self):
        cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=256)
        model = LM(cfg)
        loader = OnlineDynamicLoader(
            tiny_dataset(48), world_size=2,
            config=OdbConfig(l_max=512, buffer_size=16, prefetch_factor=8, num_workers=2),
            layout="packed", vocab_size=256,
        )
        params = model.init(jax.random.PRNGKey(0))
        from repro.train.trainer import assemble_model_batch
        steps = 0
        for ls in loader.epoch(0):
            assert len(ls.batches) == 2
            batch = assemble_model_batch(ls, loader.layout)
            assert "segments" in batch and "positions" in batch
            loss_sum, tc = model.loss_sums(params, batch)
            assert bool(jnp.isfinite(loss_sum))
            steps += 1
            if steps >= 2:
                break
        assert steps >= 1

    def test_packed_trainer_end_to_end(self):
        cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), vocab_size=256)
        model = LM(cfg)
        loader = OnlineDynamicLoader(
            tiny_dataset(), world_size=2,
            config=OdbConfig(l_max=256, buffer_size=16, prefetch_factor=8, num_workers=2),
            layout="packed", vocab_size=256,
        )
        trainer = Trainer(
            model, loader,
            OptimizerConfig(lr=3e-3, total_steps=40, warmup_ratio=0.05),
            TrainerConfig(log_every=1),
        )
        state = trainer.init_state(jax.random.PRNGKey(0))
        state, steps = trainer.train_epoch(state, epoch=0)
        losses = [h["loss"] for h in trainer.history]
        assert steps >= 2
        assert losses[-1] < losses[0], losses
        assert loader.last_audit.eta_identity == 0.0


class TestElasticReshard:
    def test_restore_into_new_topology(self, tmp_path):
        """Checkpoint under one mesh, restore sharded for another (elastic)."""
        import os
        state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        ckpt.save_checkpoint(tmp_path, 5, state)
        devs = jax.devices()
        if len(devs) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(devs[: len(devs) // 2 * 2]).reshape(2, -1), ("a", "b"))
            sh = {"w": NamedSharding(mesh, P("a", None))}
            restored, step = ckpt.restore_checkpoint(tmp_path, state, shardings=sh)
            assert restored["w"].sharding == sh["w"]
        else:
            restored, step = ckpt.restore_checkpoint(tmp_path, state)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
