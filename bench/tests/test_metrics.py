"""The per-layer readers of the program's names, on the synthesized trace of
test_named.py written where the harness writes a run's trace."""

import pathlib

import pytest
from jax.profiler import ProfileData

import cell as cells
import named
from test_named import TRACE

NAMED = {  # reader -> hand count over the trace's 2 steps, ms per step
    "realize_idle_ms": 80_000e-6 / 2,  # the gap inside train/realize
    "optimizer_ms": 2_000e-6 / 2,
    "loss_ms": 1_000e-6 / 2,
    "flash_fwd_ms": 2_000e-6 / 2,
    "flash_bwd_ms": (4_000e-6 + 3_000e-6) / 2,  # flash_dq + flash_dkv
}


def _write(root: pathlib.Path, text: str) -> None:
    path = root / "cell" / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))


@pytest.mark.parametrize("metric", sorted(NAMED))
def test_reader_counts_the_named_time_per_step(tmp_path, monkeypatch, metric):
    _write(tmp_path, TRACE)
    monkeypatch.setattr(named, "TRACE_ROOT", tmp_path)
    value = cells.load_module("metrics", metric).read({"trace": {"steps": 2}}, {})
    assert value == pytest.approx(NAMED[metric])


@pytest.mark.parametrize("metric", sorted(NAMED))
def test_reader_reads_none_without_its_names(tmp_path, monkeypatch, metric):
    text = TRACE
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "adamw", "lm_loss", "train/realize"):
        text = text.replace(name, "plain")
    _write(tmp_path, text)
    monkeypatch.setattr(named, "TRACE_ROOT", tmp_path)
    reader = cells.load_module("metrics", metric)
    assert reader.read({"trace": {"steps": 2}}, {}) is None
    assert reader.read({"window": {}}, {}) is None  # an untraced run
