"""The comparison that decides ``correct``.

What the timed path produced in its first three optimizer steps (the
same compiled step and state that the window then drives) is held
against the plain reference driven from the same seed over the same
rows.  Nine numbers are read; those that ``limits/<cell>.json`` gives a
limit are compared, the others are printed beside them (readings and
reasons in PERF.md section 2):

``loss_step1..3``     |program - reference| / |reference| of each step's
                      mean loss over the global batch.
``grad1_median_gap``  first gradient as the optimizer got it, worked out
                      from the program's optimizer state after one step
                      (the family's ``program.first_gradient``): per leaf
                      | ||g_prog|| - ||g_ref|| | over the larger of that
                      leaf's and the median leaf's reference norm; the
                      median leaf's.
``dparam_median_gap`` the parameters' change over the three steps, by
                      the same per-leaf measure, over the leaves whose
                      reference gradient is at least a thousandth of
                      the median leaf's (the others move by round-off
                      alone); the median leaf's.
``grad1_norm_gap``, ``dparam_norm_gap``  the same two by the worst leaf.
``grad1_median_diff``, ``grad1_best_diff``  the norm of the difference
                      of the two first gradients over the same
                      denominator: the median leaf's, and the smallest
                      (the leaf nearest the loss, which only the forward
                      pass's rounding reaches).

Comparing two references (the control in lower precision, a planted
fault) goes through the same function: pass the variant in the
program's place.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("loss_step1", "loss_step2", "loss_step3", "grad1_median_gap",
         "dparam_median_gap", "grad1_norm_gap", "dparam_norm_gap",
         "grad1_median_diff", "grad1_best_diff")


def limits_for(workload: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        row = json.load(f)
    unknown = set(row) - set(NAMES)
    if unknown or not row:
        raise ValueError(f"limits of {workload!r}: unknown or no "
                         f"numbers {sorted(unknown)}")
    return {k: float(v) for k, v in row.items()}


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def _leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf: | ||prog|| - ||ref|| | over the larger of that leaf's
    and the median leaf's reference norm."""
    med = float(np.median(list(ref.values())))
    out = {}
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def _worst(gaps: dict) -> tuple[float, str]:
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def program_side(captured: dict, program, cfg: dict) -> dict:
    """losses / first gradient / parameter change from what the clock
    copied out of the program (see clock.py); ``program`` is the
    family's ``program`` module."""
    p0, p_end = captured["p0"], captured["p_end"]
    return {
        "losses": list(captured["losses"]),
        "grad1": program.first_gradient(p0, captured["opt1"], cfg),
        "dparam": {k: p_end[k] - p0[k] for k in p0},
    }


def reference_side(followed: dict) -> dict:
    """The same three things from ``reference.follow``'s result."""
    return {
        "losses": list(followed["losses"]),
        "grad1": followed["grad1"],
        "dparam": {k: followed["p_end"][k] - followed["p0"][k]
                   for k in followed["p0"]},
    }


def compare(prog: dict, ref: dict) -> dict:
    """Every number read (and where the worst leaves sit)."""
    if set(prog["grad1"]) != set(ref["grad1"]):
        missing = sorted(set(prog["grad1"]) ^ set(ref["grad1"]))[:4]
        raise ValueError("program and reference disagree on the "
                         f"parameter leaves: {missing}")
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        gap = abs(a - b) / abs(b)
        out[f"loss_step{i}"] = gap if np.isfinite(gap) else float("inf")
    g_ref = _norms(ref["grad1"])
    g_gaps = _leaf_gaps(_norms(prog["grad1"]), g_ref)
    out["grad1_norm_gap"], g_where = _worst(g_gaps)
    med = float(np.median(list(g_ref.values())))
    moved = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    d_gaps = _leaf_gaps(_norms(prog["dparam"]), _norms(ref["dparam"]),
                        keep=moved)
    out["dparam_norm_gap"], d_where = _worst(d_gaps)
    out["grad1_median_gap"] = float(np.median(list(g_gaps.values())))
    diffs = [float(np.linalg.norm(
        (np.asarray(prog["grad1"][k], np.float64)
         - np.asarray(ref["grad1"][k], np.float64)).ravel()))
        / max(g_ref[k], med, 1e-30) for k in g_ref]
    out["grad1_median_diff"] = float(np.median(diffs))
    out["grad1_best_diff"] = float(np.min(diffs))
    out["dparam_median_gap"] = float(np.median(list(d_gaps.values())))
    top = lambda g: [[k, round(v, 4)] for k, v in sorted(  # noqa: E731
        g.items(), key=lambda kv: -kv[1])[:4]]
    out["_where"] = {"leaves_left_out": len(g_ref) - len(moved),
                     "grad1_top": top(g_gaps), "dparam_top": top(d_gaps)}
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number that has
    a limit is held to it (missing or not finite fails); one without
    is carried with the limit null and decides nothing."""
    table, ok = {}, True
    for k in NAMES:
        v = numbers.get(k)
        if k in limits:
            good = v is not None and np.isfinite(v) and v <= limits[k]
            ok = ok and bool(good)
        table[k] = {"value": v, "limit": limits.get(k)}
    return ok, table
