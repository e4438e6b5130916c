"""The per-layer readers of the program's names, on the synthesized trace of
test_named.py written where the harness writes a run's trace."""

import pathlib

import pytest
from jax.profiler import ProfileData

import cell as cells
from test_named import TRACE

NAMED = {  # reader -> hand count over the trace's 2 steps, ms per step
    "realize_idle_ms": 80_000e-6 / 2,  # the gap inside train/realize
    "optimizer_ms": 2_000e-6 / 2,
    "loss_ms": 1_000e-6 / 2,
    "flash_fwd_ms": 2_000e-6 / 2,
    "flash_bwd_ms": (4_000e-6 + 3_000e-6) / 2,  # flash_dq + flash_dkv
}


def write_trace(root: pathlib.Path, text: str) -> str:
    """The trace ``text`` written where the harness writes a run's trace."""
    path = root / ".bench_trace" / "cell" / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.mark.parametrize("metric", sorted(NAMED))
def test_reader_counts_the_named_time_per_step(tmp_path, metric):
    path = write_trace(tmp_path, TRACE)
    value = cells.load_module("metrics", metric).read({"trace": {"steps": 2, "path": path}}, {})
    assert value == pytest.approx(NAMED[metric])


@pytest.mark.parametrize("metric", sorted(NAMED))
def test_reader_reads_none_without_its_names(tmp_path, metric):
    text = TRACE
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "adamw", "lm_loss", "train/realize"):
        text = text.replace(name, "plain")
    path = write_trace(tmp_path, text)
    reader = cells.load_module("metrics", metric)
    assert reader.read({"trace": {"steps": 2, "path": path}}, {}) is None
    assert reader.read({"window": {}}, {}) is None  # an untraced run
