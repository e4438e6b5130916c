"""The comparison's pieces on hand-made inputs."""

import json
import pathlib

import pytest

import cell as cells
import check
import harness

REPO = pathlib.Path(__file__).resolve().parents[2]


def test_worst_leaf_gap_is_measured_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6}
    # c's gap of 1e-6 counts against the median leaf (1.0), a's 0.1 against itself
    assert check.worst_leaf_gap(prog, ref) == (pytest.approx(0.1), "a")


@pytest.mark.parametrize("traffic", ["llava.packed", "sharegpt4o.packed"])
def test_layout_check_finds_each_broken_contract(traffic):
    spec = json.loads((REPO / "bench/traffic" / f"{traffic}.json").read_text())
    loader = cells.make_loader(dict(spec, scale=spec["scale"] * 0.2), 512)
    step = next(iter(loader.streaming_epoch(0)))
    arrays, md = harness.global_arrays(step), step.metadata
    assert check.layout_violations(arrays, md, packed=True) == 0
    row, col = map(int, (arrays["segments"] > 0).nonzero()[0][:1].tolist() + [5])
    for key, value in (("positions", 999), ("loss_mask", 0.0), ("segments", 77)):
        broken = {k: v.copy() for k, v in arrays.items()}
        broken[key][row, col] = value
        assert check.layout_violations(broken, md, packed=True) > 0, key
