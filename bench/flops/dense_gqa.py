"""Operations and bytes a dense grouped-query-attention decoder requires.

Counts from the configuration's sizes (Hugging Face keys) and the documents
of a step, never from what the program runs: recomputation and padding are
not counted.  Any other architecture (latent attention, state-space layers,
experts, windowed or linear layers) is refused, not guessed.
"""

from __future__ import annotations

_FOREIGN = (
    "num_experts", "num_local_experts", "n_routed_experts", "moe_intermediate_size",
    "kv_lora_rank", "q_lora_rank", "state_size", "ssm_state_size", "mamba_d_state",
    "linear_num_key_heads", "sliding_window_pattern",
)


def _sizes(config: dict):
    foreign = [k for k in _FOREIGN if config.get(k)]
    kinds = set(config.get("layer_types") or ["full_attention"])
    if foreign or kinds != {"full_attention"} or config.get("use_sliding_window"):
        raise ValueError(
            f"dense_gqa counts dense full-attention stacks only; this config has {foreign or kinds}"
        )
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return (
        config["hidden_size"], config["num_hidden_layers"], heads,
        config["num_key_value_heads"], head_dim, config["intermediate_size"], config["vocab_size"],
    )


def matmul_params(config: dict) -> int:
    """Parameters that multiply every token: the layers' projections and the
    output projection (the embedding lookup is no product)."""
    d, layers, h, kv, dh, ff, vocab = _sizes(config)
    per_layer = 2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff
    return layers * per_layer + d * vocab


def causal_pairs(docs) -> int:
    """(query, key) pairs of causal attention within each document."""
    return sum(n * (n + 1) // 2 for n in docs)


def step_flops(config: dict, docs) -> float:
    """Operations of one training step over documents of lengths ``docs``:
    6 per matmul parameter per token, and per causal pair and layer 4 x head
    dim x heads forward, twice that backward."""
    d, layers, h, kv, dh, ff, vocab = _sizes(config)
    return 6.0 * matmul_params(config) * sum(docs) + 12.0 * dh * h * layers * causal_pairs(docs)


def flash_work(config: dict, docs, shape, elem_bytes: int) -> tuple[float, float, int]:
    """(operations, bytes, calls) of the attention kernels of one step: the
    causal same-document pairs at 4 x head dim x heads forward and twice that
    backward; q, k, v, o, dO, dq, dk, dv in ``elem_bytes`` and the float32
    log-sum-exp once each, per layer."""
    d, layers, h, kv, dh, ff, vocab = _sizes(config)
    rows, seq = shape
    ops = 12.0 * dh * h * causal_pairs(docs) * layers
    per_layer = elem_bytes * rows * seq * dh * (4 * h + 4 * kv) + 4 * rows * seq * h
    return ops, float(per_layer * layers), layers
