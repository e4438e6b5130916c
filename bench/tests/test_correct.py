"""`correct` at a size a CPU test can hold, with each cell's own limits.

The harness runs on the CPU (its look for a chip skipped) with the cell's
configuration cut to toy widths and its traffic to a small dataset.  The
sound program passes; with the timed path broken underneath, or with the
reference in the control's precision put in the program's place, `correct`
comes out false.
"""

import dataclasses
import json
import pathlib
import time

import jax.numpy as jnp
import numpy as np
import pytest

import cell as cells
import check
import harness

REPO = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
TOY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2, vocab_size=512)


def toy_cell(name):
    c = cells.load_cell(REPO, name)
    config = dict(c.config, **TOY)
    config["num_attention_heads"] = 4
    config["num_key_value_heads"] = 2 if c.config["num_key_value_heads"] < c.config["num_attention_heads"] else 4
    config["head_dim"] = 16
    traffic = dict(c.traffic, scale=c.traffic["scale"] * 0.3)
    return dataclasses.replace(c, config=config, traffic=traffic)


def run(c, tmp_path):
    return harness.run(c, seed=2147483661, seconds=1.0, trace=False, t0=time.perf_counter(),
                       root=tmp_path, require_tpu=False)


def broken(monkeypatch, fault):
    """Break the trainer's jitted step: ``unchanged`` returns the state it was
    given; ``half`` takes the mean loss over the first half of the batch's
    token slots (the first half of its rows, or of its one row)."""
    import repro.train.trainer as trainer_mod

    make = trainer_mod.make_train_step

    def make_broken(model, opt):
        step = make(model, opt)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        def half(state, batch):
            mask = batch["loss_mask"]
            keep = (jnp.arange(mask.size) < mask.size // 2).reshape(mask.shape)
            return step(state, dict(batch, loss_mask=mask * keep))

        return {"unchanged": unchanged, "half": half}[fault]

    monkeypatch.setattr(trainer_mod, "make_train_step", make_broken)


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name, tmp_path):
    result = run(toy_cell(name), tmp_path)
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_step_is_not_correct(name, fault, tmp_path, monkeypatch):
    broken(monkeypatch, fault)
    result = run(toy_cell(name), tmp_path)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    c = toy_cell(name)
    arch = cells.arch_config(c.config)
    loader = cells.make_loader(c.traffic, arch.vocab_size)
    steps = [s for _, s in zip(range(3), loader.streaming_epoch(0))]
    batches = [{k: a[k] for k in ("tokens", "segments", "loss_mask")}
               for a in map(harness.global_arrays, steps)]
    reference = c.module("references", c.config["reference"])
    ref = reference.train3(c.config, 5, batches)
    control = reference.train3(c.config, 5, batches, dtype=jnp.bfloat16)
    compared = check.compare(control, ref, 0, 0.0, c.limits)
    assert not check.passed(compared), compared
    assert np.isfinite([v["value"] for v in compared.values()]).all()
