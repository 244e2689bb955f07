"""The comparison that decides ``correct``: the program's first three
segments against the plain reference's (``fedref.py``).

Numbers compared, each against a limit of its own (``limits/<cell>.json``):

- ``first_loss0_gap``: the relative gap in round 0's first-iterate loss,
  the cohort's mean loss at the starting weights. No estimate has touched
  it yet, so it is the forward alone at the timed batch: sound runs read
  it to the last bit or nearly, and a forward computed at a lower
  precision does not.
- ``loss_gap``: the largest relative gap between the program's and the
  reference's mean local loss over every round of the three segments, and
  between their test losses at every in-scan eval.
- ``change1_gap`` / ``change3_gap``: the parameters' change after the
  first / third segment, by the worst leaf: the gap between the program's
  and the reference's change norms, over the larger of the reference's
  norm of that leaf and of the median leaf. Leaves the reference moves by
  less than a thousandth of the median leaf's change are left out.
- with a wireless channel: ``m_effective_gap``, the largest difference in
  the number of scheduled clients (exact, limit 0), and ``delta_max_gap``,
  the largest relative gap in the round's largest scheduled delta norm.
"""
from __future__ import annotations

import numpy as np


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    return float(np.max(gap)) if gap.size else 0.0


def change_gap(p_prog, p_ref, p0):
    """Worst-leaf gap of change norms (see the module docstring)."""
    leaves0 = [np.asarray(l, np.float64) for l in _leaves(p0)]
    n_prog = [np.linalg.norm(np.asarray(l, np.float64) - l0)
              for l, l0 in zip(_leaves(p_prog), leaves0)]
    n_ref = [np.linalg.norm(np.asarray(l, np.float64) - l0)
             for l, l0 in zip(_leaves(p_ref), leaves0)]
    med = float(np.median(n_ref))
    gaps = [abs(a - b) / max(b, med, 1e-30)
            for a, b in zip(n_prog, n_ref) if b >= 1e-3 * med]
    if not all(np.isfinite(n_prog)):
        return float("inf")
    return float(max(gaps)) if gaps else float("inf")


def _leaves(tree):
    if isinstance(tree, dict):
        return [v for _, v in sorted(tree.items())]
    return list(tree)


def compare(prog, ref, params0, channel: bool) -> dict:
    """``prog``/``ref``: {"metrics": per-round arrays, "params": [params
    after each segment]}. Returns {name: value}."""
    pm, rm = prog["metrics"], ref["metrics"]
    ev = ~np.isnan(rm["eval_loss"])
    out = {"first_loss0_gap": _rel(pm["first_loss"][:1],
                                   rm["first_loss"][:1]),
           "loss_gap": max(_rel(pm["mean_local_loss"], rm["mean_local_loss"]),
                           _rel(pm["eval_loss"][ev], rm["eval_loss"][ev])),
           "change1_gap": change_gap(prog["params"][0], ref["params"][0],
                                     params0),
           "change3_gap": change_gap(prog["params"][2], ref["params"][2],
                                     params0)}
    if channel:
        out["m_effective_gap"] = float(np.max(np.abs(
            np.asarray(pm["m_effective"], np.float64)
            - np.asarray(rm["m_effective"], np.float64))))
        out["delta_max_gap"] = _rel(pm["delta_max"], rm["delta_max"])
    for k, v in out.items():
        if not np.isfinite(v):
            out[k] = float("inf")
    return out


def judge(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number without a limit fails."""
    checks = {k: {"value": v, "limit": limits.get(k)} for k, v in
              values.items()}
    ok = all(c["limit"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
