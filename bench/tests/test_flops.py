"""Operation and byte counts against hand counts for a toy configuration."""

import pytest

import cell as cells

dense_gqa = cells.load_module("flops", "dense_gqa")

TOY = {
    "hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
}


def test_matmul_params_by_hand():
    # per layer: q and o 2*8*2*4 = 128, k and v 2*8*1*4 = 64, MLP 3*8*16 = 384
    assert dense_gqa.matmul_params(TOY) == 2 * (128 + 64 + 384) + 8 * 10


def test_one_packed_row_by_hand():
    docs = [3, 2]  # one row of 8 slots: documents of 3 and 2 tokens, 3 pad
    assert dense_gqa.causal_pairs(docs) == 6 + 3
    # 6 x 1,232 params x 5 tokens + 12 x head_dim 4 x 2 heads x 2 layers x 9 pairs
    assert dense_gqa.step_flops(TOY, docs) == 6 * 1232 * 5 + 12 * 4 * 2 * 2 * 9
    ops, nbytes, calls = dense_gqa.flash_work(TOY, docs, (1, 8), elem_bytes=2)
    assert ops == 12 * 4 * 2 * 9 * 2
    # per layer: q, o, dO, dq (1x8x2x4) and k, v, dk, dv (1x8x1x4) at 2 bytes,
    # the float32 log-sum-exp (1x2x8)
    assert nbytes == 2 * (2 * (4 * 64 + 4 * 32) + 4 * 16)
    assert calls == 2
    assert dense_gqa.kernel_work(TOY, docs, (1, 8), elem_bytes=2) == {"flash": (ops, nbytes)}
    assert dense_gqa.KERNELS == {"flash": "flash_fwd|flash_dq|flash_dkv"}


@pytest.mark.parametrize("extra", [{"kv_lora_rank": 512}, {"num_experts": 8},
                                   {"layer_types": ["full_attention", "linear_attention"]}])
def test_other_architectures_are_refused(extra):
    with pytest.raises(ValueError, match="dense full-attention"):
        dense_gqa.matmul_params({**TOY, **extra})
