"""A cell is found by name from its files; metric readers by the metric's name."""

import collections
import dataclasses
import json
import pathlib

import pytest

import cell as cells
from repro.configs import get_smoke_config

REPO = pathlib.Path(__file__).resolve().parents[2]


def _drop_cell(root: pathlib.Path) -> None:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    config = json.loads((REPO / "bench/configs/qwen3_0_6b_f32.json").read_text())
    config.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512)
    traffic = {"dataset": "uniform_narrow", "scale": 0.05, "record_seed": 0, "sampler_seed": 0, "world": 1,
               "l_max": 1024, "cutoff_len": None, "layout": "dense", "why": "test mix"}
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True)
    (root / "bench/configs/toy_qwen.json").write_text(json.dumps(config))
    (root / "bench/traffic/narrow.dense.json").write_text(json.dumps(traffic))
    (root / "bench/limits/toy_qwen.narrow.dense.json").write_text(json.dumps({"loss_gap": 0.5}))
    bench["configs"].append({"name": "toy_qwen", "source": "test", "file": "bench/configs/toy_qwen.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy_qwen.narrow.dense", "config": "toy_qwen",
                               "traffic": "narrow.dense", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_is_found_by_name(tmp_path):
    _drop_cell(tmp_path)
    c = cells.load_cell(tmp_path, "toy_qwen.narrow.dense")
    assert (c.config_name, c.traffic_name, c.chips) == ("toy_qwen", "narrow.dense", 1)
    assert c.traffic["layout"] == "dense" and c.limits == {"loss_gap": 0.5}
    arch = cells.arch_config(c.config)
    assert (arch.d_model, arch.n_layers, arch.n_kv_heads, arch.dtype) == (64, 2, 2, "float32")
    assert arch.qk_norm and arch.rope_theta == 1e6
    # a metric with no "workloads" key applies to every cell, a listed one only to its cells
    names = {m["name"] for m in c.per_layer}
    assert "device_idle" in names and "flash_roofline" not in names
    assert [m["name"] for m in c.end_to_end] == ["tokens_per_s", "samples_per_s", "peak_hbm_gib", "setup_s"]


def test_every_metric_has_a_reader_found_by_name():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(cells.load_module("metrics", m["name"]).read)


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cells.load_cell(REPO, w["name"])
        assert set(c.limits) >= {"loss_gap", "grad1_gap", "change_gap", "layout_bad", "audit_eta"}
        cells.arch_config(c.config)


def test_traffic_fixes_documents_and_order():
    from repro.data.pipeline import realize_lengths

    traffic = json.loads((REPO / "bench/traffic/llava.packed.json").read_text())
    docs, steps = [], []
    for sampler_seed in (0, 0, 2**31 + 5):
        loader = cells.make_loader(dict(traffic, sampler_seed=sampler_seed), 512)
        docs.append(collections.Counter(
            realize_lengths(loader.dataset.records(loader.seed), loader.policy, 0)))
        steps.append([s.metadata.total_tokens for s in loader.streaming_epoch(0)])
        assert loader.last_audit.eta_identity == 0.0
    # the same traffic file gives the same steps; another order keeps the documents
    assert steps[0] == steps[1] and steps[0] != steps[2]
    assert docs[0] == docs[2] and len(docs[0]) > 100


RECORDED_ARCH = json.loads((REPO / "bench/tests/dense_leaves.json").read_text())["arch_config"]


@pytest.mark.parametrize("name", sorted(RECORDED_ARCH))
def test_config_file_gives_the_recorded_arch_config(name):
    config = json.loads((REPO / "bench/configs" / f"{name}.json").read_text())
    assert dataclasses.asdict(cells.arch_config(config)) == RECORDED_ARCH[name]


def _dsv3_smoke_file() -> dict:
    """The DeepSeek-V3 smoke sizes as a configuration file states them."""
    s = get_smoke_config("deepseek_v3_671b")
    return {
        "model_type": "deepseek_v3", "arch": "deepseek_v3_671b", "hidden_size": s.d_model,
        "intermediate_size": s.d_ff, "num_hidden_layers": s.n_layers, "num_attention_heads": s.n_heads,
        "num_key_value_heads": s.n_kv_heads, "vocab_size": s.vocab_size, "rope_theta": 10000,
        "torch_dtype": "float32", "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-6,
        "n_routed_experts": s.n_experts, "num_experts_per_tok": s.top_k,
        "moe_intermediate_size": s.moe_d_ff, "n_shared_experts": s.n_shared_experts,
        "first_k_dense_replace": s.first_k_dense, "moe_layer_freq": 1, "kv_lora_rank": s.kv_lora_rank,
        "q_lora_rank": s.q_lora_rank, "qk_rope_head_dim": s.qk_rope_dim, "qk_nope_head_dim": s.qk_nope_dim,
        "v_head_dim": s.v_head_dim, "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 0,
        "ep_size": 1, "max_position_embeddings": 4096, "tie_word_embeddings": False,
        "source": "test", "flops": "none", "reference": "toy_mla",
    }


@pytest.fixture
def mla_root(tmp_path) -> pathlib.Path:
    """A checkout whose one reference, ``toy_mla``, covers ``deepseek_v3``."""
    path = tmp_path / "bench/references/toy_mla.py"
    path.parent.mkdir(parents=True)
    path.write_text('MODEL_TYPES = {"deepseek_v3": {"attn_kind": "mla", "norm": "rms", "qk_norm": False}}\n')
    return tmp_path


def test_moe_and_mla_sizes_reach_the_arch_config(mla_root):
    smoke = get_smoke_config("deepseek_v3_671b")
    arch = cells.arch_config(_dsv3_smoke_file(), mla_root)
    for field in ("d_model", "d_ff", "n_layers", "n_heads", "vocab_size", "n_experts", "top_k", "moe_d_ff",
                  "n_shared_experts", "first_k_dense", "moe_every", "kv_lora_rank", "q_lora_rank",
                  "qk_rope_dim", "qk_nope_dim", "v_head_dim"):
        assert getattr(arch, field) == getattr(smoke, field), field
    assert (arch.rope_theta, arch.dtype, arch.attn_kind) == (10000.0, "float32", "mla")
    assert cells.arch_config({**_dsv3_smoke_file(), "q_lora_rank": None}, mla_root).q_lora_rank == 0


@pytest.mark.parametrize("key, value", [
    ("num_local_experts", 8),  # a size with no field
    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("routed_scaling_factor", 2.446),
    ("norm_topk_prob", True), ("seq_aux", True),  # routing and loss the program lacks
    # fields of ArchConfig that set how the program runs, not what it computes
    ("attn_impl", "xla"), ("attn_block_q", 64), ("remat", False), ("logits_fp32", False),
    ("capacity_factor", 1.0), ("sequence_sharding", True), ("name", "other"),
])
def test_a_key_arch_config_does_not_know_is_refused(key, value, mla_root):
    with pytest.raises(ValueError, match=key):
        cells.arch_config({**_dsv3_smoke_file(), key: value}, mla_root)


@pytest.mark.parametrize("key, value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("clip_qkv", 8.0), ("rms_norm_eps", 1e-5),
    ("n_group", 8), ("topk_group", 4), ("num_nextn_predict_layers", 1), ("model_type", "qwen3"),
    ("moe_layer_freq", 2),  # MoE layers placed otherwise than the program's moe_every
])
def test_a_checked_key_the_program_does_otherwise_is_refused(key, value, mla_root):
    with pytest.raises(ValueError, match=key):
        cells.arch_config({**_dsv3_smoke_file(), key: value}, mla_root)


def test_a_model_type_is_one_the_configurations_reference_covers(mla_root):
    # the dense reference does not cover deepseek_v3, so the same file naming it is refused
    with pytest.raises(ValueError, match="model_type"):
        cells.arch_config({**_dsv3_smoke_file(), "reference": "dense_decoder"})
    (mla_root / "bench/references/toy_mla_qk.py").write_text(
        'MODEL_TYPES = {"deepseek_v3": {"attn_kind": "mla", "norm": "rms", "qk_norm": True}}\n')
    with pytest.raises(ValueError, match="model_type"):  # the program has no qk-norm here
        cells.arch_config({**_dsv3_smoke_file(), "reference": "toy_mla_qk"}, mla_root)
