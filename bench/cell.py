"""A cell of the benchmark: one model configuration under one traffic mix.

Whatever belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it, so a later change adds files and edits none:

  <config file named in BENCHMARK.json>   sizes as run, source, departures
  bench/traffic/<traffic>.json            dataset clone, scale, ranks, budget, layout
  bench/limits/<cell>.json                the limit of each number that decides `correct`
  bench/metrics/<metric>.py               the reader of one per-layer metric
  bench/flops/<module>.py                 operation counts of one architecture family

Data files are read under ``root`` (the checkout); the readers and counters
are code of this directory.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

CODE_DIR = pathlib.Path(__file__).resolve().parent

# Keys of a Hugging Face ``config.json`` and the ``ArchConfig`` field each sets.
_HF_TO_ARCH = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple[dict, ...]  # the metrics this cell reports with --trace 0
    per_layer: tuple[dict, ...]  # ... and with --trace 1


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    work = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_entry = configs[work["config"]]
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config_name=work["config"],
        config=_read_json(root / config_entry["file"]),
        traffic_name=work["traffic"],
        traffic=_read_json(root / "bench" / "traffic" / f"{work['traffic']}.json"),
        limits=_read_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a metric reader or a counter)."""
    path = CODE_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch_config(config: dict):
    """The program's ``ArchConfig`` for this configuration file: the repo's
    config for ``arch`` with every size the file states put in."""
    from repro.configs import get_config

    if config.get("tie_word_embeddings"):
        raise ValueError("repro.models.LM has no tied embeddings")
    fields = {field: config[key] for key, field in _HF_TO_ARCH.items() if key in config}
    fields["d_head"] = config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]
    fields["rope_theta"] = float(config["rope_theta"])
    return dataclasses.replace(get_config(config["arch"]), **fields)


def optimizer_config(config: dict):
    from repro.train.optimizer import OptimizerConfig

    opt = dict(config["optimizer"])
    opt["betas"] = tuple(opt["betas"])
    return OptimizerConfig(**opt)


def make_loader(traffic: dict, vocab_size: int):
    """The ODB loader as ``repro.launch.train.build_loader`` builds it from
    the launcher's defaults, with the traffic file's values put in.

    The traffic file fixes the documents (``record_seed``: the dataset's
    realized lengths) and their order (``sampler_seed``: the sampler's
    shuffle), so every run of a cell trains on the same steps; the run's
    seed draws the weights.  With the order drawn from the run's seed, the
    window's prefix of the epoch held other documents on every seed, and
    seeds differed by 5% in tokens/s where one seed repeated to 0.1%.
    """
    from repro.launch.train import build_loader, parse_args

    args = parse_args([
        "--dataset", traffic["dataset"],
        "--data-scale", str(traffic["scale"]),
        "--world", str(traffic["world"]),
        "--l-max", str(traffic["l_max"]),
        "--layout", traffic["layout"],
    ])
    loader = build_loader(args, vocab_size)
    make_records = loader.dataset.make_records
    record_seed = int(traffic["record_seed"])
    loader.dataset = dataclasses.replace(
        loader.dataset, make_records=lambda size, _seed: make_records(size, record_seed)
    )
    if traffic.get("cutoff_len"):
        loader.policy = dataclasses.replace(loader.policy, cutoff_len=int(traffic["cutoff_len"]))
    loader.seed = int(traffic["sampler_seed"])
    return loader
