"""A cell is found by name from its files; metric readers by the metric's name."""

import collections
import json
import pathlib

import cell as cells

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
