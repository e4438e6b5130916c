"""Ahead-of-time compiles of the flash kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) cannot see what the chip's compiler
refuses: block shapes that are not (8, 128)-aligned, SMEM or VMEM overflow.
These tests compile the main-path kernels at Qwen3-0.6B attention width
(16 heads, 8 KV heads, head_dim 128, bf16) for a v5e chip that is described,
not attached, and assert that each program holds a Mosaic kernel
(``tpu_custom_call``) named for its pass (``flash_fwd``, ``flash_dq``,
``flash_dkv``); and, in float32 at the tiles the model's block rule picks,
at the widest row of each benchmark cell and at a row that 512 does not
divide.  No chip is needed; nothing runs.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU compiler library.
"""

from __future__ import annotations

import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa

REPO = pathlib.Path(__file__).resolve().parents[1]
H, KV, D = 16, 8, 128  # Qwen3-0.6B attention

FWD = {
    "dense": fa.segment_flash_attention,
    "pruned": fa.segment_flash_attention_pruned,
}
BWD = {
    "dense": fa.segment_flash_attention_bwd,
    "pruned": fa.segment_flash_attention_bwd_pruned,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off
    (a program compiled for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _qkv_seg(b, s):
    return [
        ((b, s, H, D), jnp.bfloat16),
        ((b, s, KV, D), jnp.bfloat16),
        ((b, s, KV, D), jnp.bfloat16),
        ((b, s), jnp.int32),
    ]


def _bwd_operands(b, s):
    # q, k, v, segment ids, out, lse, dO
    return _qkv_seg(b, s) + [
        ((b, s, H, D), jnp.bfloat16),
        ((b, s, H), jnp.float32),
        ((b, s, H, D), jnp.bfloat16),
    ]


def _compile_bwd(sharding, grid, b, s) -> str:
    return _compile(sharding, BWD[grid], *_bwd_operands(b, s))


def _kernel_names(text: str) -> list[str]:
    """The name of each Mosaic kernel call of a compiled program, taken from
    its op_name metadata (``.../flash_fwd/pallas_call``), which the profiler
    trace shows on the kernel's device events."""
    return [
        re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)/pallas_call"', line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.parametrize("grid", ["dense", "pruned"])
def test_forward_compiles(one_chip, grid):
    text = _compile(
        one_chip,
        lambda q, k, v, seg: FWD[grid](q, k, v, seg, return_residuals=True),
        *_qkv_seg(1, 4096),
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == ["flash_fwd"]


@pytest.mark.parametrize("grid", ["dense", "pruned"])
def test_backward_compiles(one_chip, grid):
    text = _compile_bwd(one_chip, grid, 1, 4096)
    assert text.count("tpu_custom_call") == 2  # dQ pass + dK/dV pass
    assert sorted(_kernel_names(text)) == ["flash_dkv", "flash_dq"]


@pytest.mark.parametrize(
    "b, s, kv", [(1, 8192, 16), (2, 2048, 8), (2, 1280, 8)]
)
def test_pruned_compiles_at_rule_blocks_f32(one_chip, b, s, kv):
    """The tiles the model's rule picks (512 x 1024 at 8k rows, 512 x 512 at
    2k) compile in float32 for both passes, at the widest row of each
    benchmark cell: OLMo-1B's 16/16 heads and Qwen3-0.6B's 16/8; and at a
    row that 512 does not divide (1280: 320 x 256, a lane-wide kv tile)."""
    from repro.kernels.autotune import heuristic_blocks

    bq, bk = heuristic_blocks(s)
    f32 = jnp.float32
    q, kv_ = ((b, s, H, D), f32), ((b, s, kv, D), f32)
    seg = ((b, s), jnp.int32)
    fwd = _compile(
        one_chip,
        lambda q, k, v, seg: fa.segment_flash_attention_pruned(
            q, k, v, seg, block_q=bq, block_kv=bk, return_residuals=True
        ),
        q, kv_, kv_, seg,
    )
    assert _kernel_names(fwd) == ["flash_fwd"]
    bwd = _compile(
        one_chip,
        lambda *a: fa.segment_flash_attention_bwd_pruned(
            *a, block_q=bq, block_kv=bk
        ),
        q, kv_, kv_, seg, q, ((b, s, H), f32), q,
    )
    assert sorted(_kernel_names(bwd)) == ["flash_dkv", "flash_dq"]


def _smoke_packed_shapes() -> set[tuple[int, int]]:
    """Every (rows, row_capacity) of the epoch chip_smoke.py trains on."""
    from repro.configs import get_config
    from repro.launch.train import build_loader, parse_args

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    args = parse_args(smoke.TRAIN_ARGS + ["--layout", "packed"])
    loader = build_loader(args, get_config(args.arch).vocab_size)
    return {
        (sum(b.tokens.shape[0] for b in step.batches),
         step.batches[0].tokens.shape[1])
        for step in loader.streaming_epoch(0)
    }


def test_pruned_backward_compiles_at_smoke_largest_shape(one_chip):
    rows, cap = max(_smoke_packed_shapes(), key=lambda rc: (rc[0] * rc[1], rc[1]))
    assert rows * cap <= 4096  # the step size whose memory chip_smoke.py relies on
    assert "tpu_custom_call" in _compile_bwd(one_chip, "pruned", rows, cap)


def test_pruned_backward_splits_rows_to_fit_smem(one_chip):
    """16 rows of 16k tokens: one call's liveness tables would fill all of
    SMEM, so the pruned grid runs several row-group calls per pass."""
    text = _compile_bwd(one_chip, "pruned", 16, 16384)
    assert text.count("tpu_custom_call") > 2
    names = _kernel_names(text)  # every row group's call carries its pass's name
    assert set(names) == {"flash_dq", "flash_dkv"}
    assert names.count("flash_dq") == names.count("flash_dkv") > 1
