"""The comparison that decides `correct`.

Each number compared is a gap between what the timed path produced and the
plain reference the configuration names (``bench/references/``), and each
has a limit of its own in ``bench/limits/<cell>.json``:

  loss_gap     worst of the first three steps: |loss - ref| / ref
  grad1_gap    worst leaf of the first gradient as the optimizer takes it:
               | |g| - |g_ref| | / max(|g_ref|, median leaf |g_ref|)
  change_gap   worst leaf of the parameters' change over three steps, as the
               fourth step receives them, measured the same way; leaves whose
               reference gradient is under a thousandth of the median leaf's
               move by round-off alone and are left out
  layout_bad   positions of the three steps' arrays that break the layout's
               contract (segment, position, loss mask, token counts); exact
  audit_eta    the epoch's DGAP audit (eta_identity + eta_quota); exact
"""

from __future__ import annotations

import statistics

import numpy as np

from reference import derived_positions

NAMES = ("loss_gap", "grad1_gap", "change_gap", "layout_bad", "audit_eta")
ROUND_OFF_GRAD = 1e-3  # a leaf whose gradient is below this share of the median leaf's


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    leaves = list(ref) if leaves is None else list(leaves)
    floor = statistics.median(ref[k] for k in leaves)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def layout_violations(arrays: dict, metadata, packed: bool) -> int:
    """Positions where one step's global arrays break the layout contract:
    segment ids are non-zero exactly on loss-bearing tokens, each document is
    one run of one id in one row, packed positions restart at 0 at each
    document, and each rank's block of rows holds the real tokens and
    documents the step's metadata reports for that rank."""
    seg, mask = arrays["segments"], arrays["loss_mask"]
    bad = int(np.sum((seg > 0) != (mask > 0)))
    if packed:
        real = seg > 0
        bad += int(np.sum((arrays["positions"] != derived_positions(seg)) & real))
    ranks = len(metadata.samples_per_rank)
    rows = seg.shape[0] // ranks
    for r in range(ranks):
        docs = 0
        for row in seg[r * rows : (r + 1) * rows]:
            ids = row[row > 0]
            runs = int(np.count_nonzero(np.diff(ids))) + (1 if ids.size else 0)
            docs += runs
            bad += runs - len(np.unique(ids))  # an id that comes back is a split document
        tokens = int(mask[r * rows : (r + 1) * rows].sum())
        bad += abs(tokens - metadata.tokens_per_rank[r]) + abs(docs - metadata.samples_per_rank[r])
    return bad


def compare(program: dict, ref: dict, layout_bad: int, audit_eta: float, limits: dict) -> dict:
    """The numbers compared, each with its limit, in the order ``NAMES``."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(program["loss"], ref["loss"]))
    grad1_gap, grad1_leaf = worst_leaf_gap(program["grad1"], ref["grad1"])
    floor = statistics.median(ref["grad1"].values())
    moving = [k for k, g in ref["grad1"].items() if g >= ROUND_OFF_GRAD * floor]
    change_gap, change_leaf = worst_leaf_gap(program["change"], ref["change"], moving)
    values = {
        "loss_gap": loss_gap,
        "grad1_gap": grad1_gap,
        "change_gap": change_gap,
        "layout_bad": layout_bad,
        "audit_eta": audit_eta,
    }
    out = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    out["grad1_gap"]["leaf"] = grad1_leaf
    out["change_gap"]["leaf"] = change_leaf
    return out


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
