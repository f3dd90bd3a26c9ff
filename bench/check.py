"""The numbers that decide ``correct``, each held to its own limit.

* ``loss_gap``: the worst of the first steps' mean token losses, as
  |program - reference| / |reference|.
* ``grad_norm_gap``: the first gradient, as the optimizer got it (read
  from its state after one step); per leaf |norm_program - norm_ref| over
  the larger of the reference leaf's norm and the median leaf's, worst
  leaf.
* ``step_change_gap``: the same of the parameters' change over the
  first steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move by round-off alone).
* ``wire_gx_gap``: the norm of the first cotangent sent back over the
  wire, relative to the reference's (cells whose first stage is not 0).
"""
from __future__ import annotations

import math

import numpy as np

GRAD_FLOOR = 1e-3


def _worst(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in ref]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep]
    return max(gaps) if gaps else 0.0


def numbers(prog: dict, ref: dict) -> dict:
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError(
            "program and reference parameter trees differ: "
            f"{sorted(set(prog['grad']) ^ set(ref['grad']))[:6]}")
    out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in
                           zip(prog["loss"], ref["loss"], strict=True))}
    out["grad_norm_gap"] = _worst(prog["grad"], ref["grad"], ref["grad"])
    gmed = float(np.median(list(ref["grad"].values())))
    moving = [k for k in ref["change"] if ref["grad"][k] >= GRAD_FLOOR * gmed]
    out["step_change_gap"] = _worst(prog["change"], ref["change"], moving)
    if ref.get("wire") is not None:
        out["wire_gx_gap"] = abs(prog["wire"] - ref["wire"]) / ref["wire"]
    return out


def worst_leaves(prog: dict, ref: dict, key: str, n: int = 4) -> list:
    """[path, program norm, reference norm, gap] of the ``n`` leaves
    with the widest gap in ``key`` ("grad" or "change")."""
    med = float(np.median(list(ref[key].values())))
    rows = [[k, prog[key][k], ref[key][k],
             abs(prog[key][k] - ref[key][k]) / max(ref[key][k], med, 1e-30)]
            for k in ref[key]]
    return sorted(rows, key=lambda r: -r[3])[:n]


def decide(nums: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}).  A missing or non-finite
    number, or a number without a limit, is not correct."""
    checks, ok = {}, True
    for name, value in nums.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok and bool(nums), checks
