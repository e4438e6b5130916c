"""Leaf names of the comparison: the program's tree read through its stack
plan, and the reference's leaves named alike.

``dense_leaves.json`` holds readings recorded at toy size for both dense
configurations: the program's leaf norms of its initial weights and the
dense reference's three steps (loss, first gradient, change), each under
its leaf name, and each configuration file's ``ArchConfig``.  A change to
the naming or to the reference's arithmetic moves them.
"""

import dataclasses
import json
import pathlib

import jax
import numpy as np
import pytest

import cell as cells
import harness
from repro.configs import get_smoke_config
from repro.models import LM
from repro.models.blocks import stack_plan

REPO = pathlib.Path(__file__).resolve().parents[2]
RECORDED = json.loads(pathlib.Path(__file__).with_name("dense_leaves.json").read_text())
TOY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2, vocab_size=512)


def toy_config(name: str) -> dict:
    config = json.loads((REPO / "bench/configs" / f"{name}.json").read_text())
    gqa = config["num_key_value_heads"] < config["num_attention_heads"]
    return dict(config, **TOY, num_attention_heads=4, num_key_value_heads=2 if gqa else 4, head_dim=16)


def first_batches(vocab_size: int) -> list[dict]:
    traffic = json.loads((REPO / "bench/traffic/llava.packed.json").read_text())
    loader = cells.make_loader(dict(traffic, scale=0.003), vocab_size)
    steps = [s for _, s in zip(range(3), loader.streaming_epoch(0))]
    return [{k: a[k] for k in ("tokens", "segments", "loss_mask")} for a in map(harness.global_arrays, steps)]


def names_of(cfg) -> list[str]:
    params = LM(cfg).abstract_params()
    plan = stack_plan(cfg)
    return [n for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
            for n in harness.leaf_names(path, plan)]


@pytest.mark.parametrize("name", sorted(RECORDED["toy"]))
def test_dense_names_and_readings_are_as_recorded(name):
    config = toy_config(name)
    arch = cells.arch_config(config)
    params = jax.jit(LM(arch).init)(jax.random.PRNGKey(7))
    recorded = RECORDED["toy"][name]
    assert harness.leaf_norms(params, stack_plan(arch)) == recorded["program_norms"]
    reference = cells.load_module("references", config["reference"])
    assert reference.train3(config, 7, first_batches(arch.vocab_size)) == recorded["reference"]


def test_moe_tree_names_each_leaf_once_by_its_layer():
    cfg = get_smoke_config("deepseek_v3_671b")  # a dense prefix layer, then MoE layers
    names = names_of(cfg)
    assert len(names) == len(set(names))
    assert {"layer00.w_dq", "layer00.kv_norm", "layer00.w_in", "layer00.attn_norm", "layer00.mlp_norm",
            "layer01.moe.w_in", "layer01.moe.shared.w_in", "layer01.moe.router", "layer02.moe.w_in",
            "layer02.w_uk", "embed", "unembed", "final_norm"} <= set(names)
    assert not any(n.startswith("layer00.moe") for n in names)
    assert {n.split(".")[0] for n in names if n.startswith("layer")} == {"layer00", "layer01", "layer02"}


def test_units_of_several_layers_number_each_layer():
    # one dense prefix layer, then units of two layers: MoE, dense
    cfg = dataclasses.replace(get_smoke_config("deepseek_v3_671b"), n_layers=5, moe_every=2)
    plan = stack_plan(cfg)
    assert plan.unit_layers == ((1, 2), (3, 4))
    params = jax.jit(LM(cfg).init)(jax.random.PRNGKey(3))
    norms = harness.leaf_norms(params, plan)
    assert len(norms) == len(names_of(cfg))
    stack = params["stack"]
    for name, leaf in (("layer03.moe.w_in", stack["sub0"]["moe"]["w_in"][1]),
                       ("layer02.w_in", stack["sub1"]["mlp"]["w_in"][0]),
                       ("layer04.wo", stack["sub1"]["mixer"]["wo"][1]),
                       ("layer00.wo", params["prefix"][0]["sub0"]["mixer"]["wo"])):
        assert norms[name] == pytest.approx(float(np.linalg.norm(np.asarray(leaf))), rel=1e-6)
    assert not any(n.startswith(("layer02.moe", "layer04.moe", "layer01.w_in")) for n in norms)
