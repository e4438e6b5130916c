"""A configuration brings its own parts as files: a benchmark built here, outside
the repository's ``bench/``, with its own configuration, traffic, limits,
reference, operation counter with a kernel family, and a reader of a scope,
runs through ``harness.run`` and finds every part by name.

The run is on the CPU (the harness's look for a chip skipped); the CPU
profiler records no device plane, so the profiler is stood in for by one
that writes a synthesized trace, in which the cell's kernel and scope carry
time, a Mosaic call outside every family carries time too, and the flash
kernels and the built-in scopes do not appear.
"""

import dataclasses
import json
import pathlib
import time

import jax
import pytest
from jax.profiler import ProfileData

import cell as cells
import harness
import run as run_cmd

REPO = pathlib.Path(__file__).resolve().parents[2]
CELL = "toy_plug.narrow.packed"

REFERENCE = '''"""The toy architecture's reference: a model type of its own, computed as
the dense decoder's Qwen3, found by name."""
import cell

MODEL_TYPES = {"toy_qwen": {"attn_kind": "gqa", "norm": "rms", "qk_norm": True}}
CALLS = []


def train3(config, seed, batches, **kw):
    CALLS.append(seed)
    dense = cell.load_module("references", "dense_decoder")
    return dense.train3(dict(config, model_type="qwen3"), seed, batches, **kw)
'''

FLOPS = '''KERNELS = {"toy_kernel": "toy_kernel|toy_kernel_bwd"}


def step_flops(config, docs):
    return 1e6 * sum(docs)


def kernel_work(config, docs, shape, elem_bytes):
    return {"toy_kernel": (1e3 * sum(docs), shape[0] * shape[1])}
'''

PLAIN_FLOPS = '''def step_flops(config, docs):
    return 1e6 * sum(docs)
'''

READERS = {
    "toy_scope_ms": 'import named\n\n\ndef read(run, peaks):\n    return named.scope_ms(run, "toy_scope")\n',
    "toy_kernel_roofline": 'import named\n\n\ndef read(run, peaks):\n    return named.roofline(run, peaks, "toy_kernel")\n',
}

# Device ops (ns): the cell's kernel 1,000-3,000; a Mosaic call of no family
# 3,000-4,000; a fusion under the scope toy_scope 4,000-4,500.  The window
# span covers 500-10,500.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%toy_kernel.4 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%other_kernel.1 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stats { metadata_id: 9 str_value: "jit(train_step)/toy_scope/mul:" } } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
"""


def _write(path: pathlib.Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def build_benchmark(root: pathlib.Path) -> None:
    config = json.loads((REPO / "bench/configs/qwen3_0_6b_f32.json").read_text())
    config.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, vocab_size=512,
                  model_type="toy_qwen", reference="toy_ref", flops="toy_flops")
    traffic = dict(json.loads((REPO / "bench/traffic/llava.packed.json").read_text()), scale=0.003)
    limits = json.loads((REPO / "bench/limits/qwen3_0_6b_f32.llava.packed.json").read_text())
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 4,
        "configs": [{"name": "toy_plug", "source": "test", "file": "bench/configs/toy_plug.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": CELL, "config": "toy_plug", "traffic": "narrow.packed", "chips": 1,
                       "why": "test"}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.01,
                        "source": "host_clock"}],
        "per_layer": [
            {"name": "toy_scope_ms", "unit": "ms", "better": "lower", "source": "device_trace",
             "layer": "toy", "moves": "tokens_per_s"},
            {"name": "toy_kernel_roofline", "unit": "%", "better": "higher", "source": "device_trace",
             "layer": "toy", "moves": "tokens_per_s"},
        ],
    }
    _write(root / "BENCHMARK.json", json.dumps(bench))
    _write(root / "bench/configs/toy_plug.json", json.dumps(config))
    _write(root / "bench/traffic/narrow.packed.json", json.dumps(traffic))
    _write(root / f"bench/limits/{CELL}.json", json.dumps(limits))
    _write(root / "bench/references/toy_ref.py", REFERENCE)
    _write(root / "bench/flops/toy_flops.py", FLOPS)
    _write(root / "bench/flops/plain_flops.py", PLAIN_FLOPS)
    for name, text in READERS.items():
        _write(root / f"bench/metrics/{name}.py", text)


@pytest.fixture
def synthetic_profiler(monkeypatch):
    """``jax.profiler`` start/stop that write ``TRACE`` where the run traces."""
    logdirs = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir, **kw: logdirs.append(logdir))

    def stop_trace():
        path = pathlib.Path(logdirs[-1]) / "plugins" / "profile" / "1" / "host.xplane.pb"
        path.parent.mkdir(parents=True)
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    return logdirs


def test_a_cell_of_added_files_is_run_checked_and_traced(tmp_path, monkeypatch, synthetic_profiler):
    build_benchmark(tmp_path)
    cell = cells.load_cell(tmp_path, CELL)
    result = harness.run(cell, seed=2147483659, seconds=4.0, trace=True, t0=time.perf_counter(),
                         root=tmp_path, require_tpu=False)

    assert result["correct"], result["compared"]
    assert cells.load_module("references", "toy_ref", tmp_path).CALLS == [2147483659]
    tr = result["trace"]
    assert tr["path"].startswith(str(tmp_path / ".bench_trace" / CELL))
    assert tr["steps"] > 0
    # the family's kernel only: no Mosaic call of no family, no flash
    assert tr["kernel_s"] == pytest.approx({"toy_kernel": 2_000e-9})
    assert len(tr["kernel_work"]) == tr["steps"] and all(set(w) == {"toy_kernel"} for w in tr["kernel_work"])

    peaks = json.loads((REPO / "bench/peaks.json").read_text())["TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks)
    line = run_cmd.result_line(cell, result, trace=True)
    least = sum(max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                for ops, nbytes in (w["toy_kernel"] for w in tr["kernel_work"]))
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {"toy_scope_ms": "ms", "toy_kernel_roofline": "%"}
    assert line["metrics"]["toy_scope_ms"]["value"] == pytest.approx(1000.0 * 500e-9 / tr["steps"])
    assert line["metrics"]["toy_kernel_roofline"]["value"] == pytest.approx(100.0 * least / 2_000e-9)

    # a flops module that declares no kernel family counts no kernel work
    plain = dataclasses.replace(cell, config=dict(cell.config, flops="plain_flops"))
    red = harness._read_trace(pathlib.Path(synthetic_profiler[-1]), plain, cells.arch_config(cell.config, tmp_path), [])
    assert red["kernel_s"] == {} and red["kernel_work"] == []
