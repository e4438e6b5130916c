"""A cell of the benchmark: one model configuration under one traffic mix.

Whatever belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` or the configuration file gives it:

  <config file named in BENCHMARK.json>   sizes as run, source, departures, and
                                          the names of its reference and counter
  bench/references/<reference>.py         the plain reference of its architecture, and
                                          the model types it covers (``MODEL_TYPES``);
                                          ``reference.py`` holds what they share
  bench/flops/<flops>.py                  operations of one architecture family, and
                                          the kernel families it has work counts for
  bench/traffic/<traffic>.json            dataset clone, scale, ranks, budget, layout
  bench/limits/<cell>.json                the limit of each number that decides `correct`
  bench/metrics/<metric>.py               the reader of one per-layer metric; a
                                          reader of any kernel or scope label uses
                                          ``named.kernel_ms`` / ``named.scope_ms``

So a cell of an architecture the program already runs needs only new files,
where every key of its configuration file is one ``arch_config`` knows: a
size it maps into the program's ``ArchConfig``, a setting it checks against
what the program does, or bookkeeping.  A key it does not know is refused,
and so is a mapped key whose ``ArchConfig`` field does not exist yet; mapping
a new key needs an entry in ``_HF_TO_ARCH`` here.  A model type is one the
configuration's reference lists, so a new one comes with its reference.  An
architecture the program does not run needs the program changed first.

Data files are read under ``root`` (the checkout); the modules are read
under ``root/bench`` too, so a benchmark outside the repository finds its
own.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import inspect
import json
import pathlib
import sys

CODE_DIR = pathlib.Path(__file__).resolve().parent

# Keys of a Hugging Face ``config.json`` and the ``ArchConfig`` field each
# sets.  The last five name routing and loss semantics the program has no
# field for yet; such a key is refused until ``ArchConfig`` gains its field.
# No other field can be set from a configuration file.
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
    "n_routed_experts": "n_experts",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_rope_head_dim": "qk_rope_dim",
    "qk_nope_head_dim": "qk_nope_dim",
    "v_head_dim": "v_head_dim",
    "scoring_func": "scoring_func",
    "topk_method": "topk_method",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "seq_aux": "seq_aux",
}

# Keys that say nothing the program runs: where the file came from, what it
# stands for, what the harness reads from it, and settings with no effect on
# training (``ep_size``: the expert-parallel layout of the original code).
_BOOKKEEPING = frozenset({
    "arch", "architectures", "source", "published", "why_changed", "assumed", "deployment",
    "optimizer", "flops", "reference", "max_position_embeddings", "tie_word_embeddings",
    "ep_size",
})


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
    root: pathlib.Path  # the checkout the cell's files are read from

    def module(self, kind: str, name: str):
        """The module ``bench/<kind>/<name>.py`` of this cell's checkout."""
        return load_module(kind, name, self.root)


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
        root=root,
    )


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, root: pathlib.Path | None = None):
    """The module ``bench/<kind>/<name>.py`` (a reference, a counter or a
    metric reader) of the checkout ``root``, this directory's by default."""
    path = (CODE_DIR if root is None else pathlib.Path(root) / "bench") / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # where a dataclass of the module looks itself up
    spec.loader.exec_module(module)
    return module


def _checks(config: dict, arch, model_types: dict) -> dict:
    """Keys the program takes no value from but fixes, each with whether the
    file's value is the program's.  ``model_types`` is what the reference
    covers: each model type with the ``ArchConfig`` values the program must
    have for it."""
    from repro.models.layers import rms_norm

    rms_eps = inspect.signature(rms_norm).parameters["eps"].default
    kind = model_types.get(config.get("model_type"))
    return {
        "model_type": kind is not None and all(getattr(arch, k) == v for k, v in kind.items()),
        "hidden_act": config.get("hidden_act") == arch.act,
        "attention_bias": config.get("attention_bias") is False,
        "clip_qkv": config.get("clip_qkv") is None,
        "rms_norm_eps": arch.norm == "rms" and config.get("rms_norm_eps") == rms_eps,
        "num_nextn_predict_layers": config.get("num_nextn_predict_layers") == 0,  # no MTP heads
        "n_group": config.get("n_group") == 1,  # one expert group: plain top-k
        "topk_group": config.get("topk_group") == 1,
        # Hugging Face places an MoE layer where layer % freq == 0, the program
        # where (layer - first_k_dense) % moe_every == 0: they agree at 1
        "moe_layer_freq": config.get("moe_layer_freq") == 1 and arch.moe_every == 1,
    }


def arch_config(config: dict, root: pathlib.Path | None = None):
    """The program's ``ArchConfig`` for this configuration file: the repo's
    config for ``arch`` with every size the file states put in.

    Every key of the file is mapped to a field, checked against what the
    program does, or bookkeeping; any other key (a routing rule, a loss
    term, a size the program has no field for) raises, so nothing the file
    states is left out unnoticed.  Which model types the configuration can
    hold, and what the program must be for each, is the ``MODEL_TYPES`` of
    its reference, ``bench/references/<reference>.py`` under ``root``.
    """
    from repro.configs import get_config

    if config.get("tie_word_embeddings"):
        raise ValueError("repro.models.LM has no tied embeddings")
    base = get_config(config["arch"])
    field_names = {f.name for f in dataclasses.fields(base)}
    fields, rest, lacking = {}, [], []
    for key, value in config.items():
        field = _HF_TO_ARCH.get(key)
        if field in field_names:
            fields[field] = 0 if value is None else value  # null: the part is absent
        elif field is not None:
            lacking.append(key)
        elif key not in _BOOKKEEPING:
            rest.append(key)
    if lacking:
        raise ValueError(f"configuration keys {lacking} state semantics ArchConfig has no field for")
    fields["d_head"] = config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]
    fields["rope_theta"] = float(config["rope_theta"])
    arch = dataclasses.replace(base, **fields)
    model_types = load_module("references", config["reference"], root).MODEL_TYPES
    checks = _checks(config, arch, model_types)
    unknown = [k for k in rest if k not in checks]
    if unknown:
        raise ValueError(
            f"configuration keys {unknown} are no size the program takes, no setting it "
            f"checks and no bookkeeping (bench/cell.py: arch_config)"
        )
    bad = {k: config.get(k) for k in {*rest, "model_type"} if not checks[k]}
    if bad:
        raise ValueError(f"the program does not do what these keys state for arch "
                         f"{config['arch']!r} (model types of reference {config['reference']!r}: "
                         f"{model_types}): {bad}")
    return arch


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
