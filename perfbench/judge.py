"""The comparison that decides ``correct``: every answer the window produced
against the plain reference's, each number beside its limit."""

from __future__ import annotations

import numpy as np


def with_intercept(coef, intercept) -> np.ndarray:
    return np.append(np.asarray(coef, np.float64), float(intercept))


def compare(answers: list, ref: dict, limits: dict) -> dict:
    """``{name: {"value", "limit", "ok"}}`` over all ``answers`` (the worst
    one counts):

    - ``coef_gap``: distance of the model (coefficients and intercept) from
      the reference's optimum, over the optimum's norm;
    - ``objective_gap``: the objective the fit reports for its model against
      the reference's objective at that same model, over the latter.
    """
    if not answers:
        return {"answers": {"value": 0, "limit": 1, "ok": False}}
    want = with_intercept(ref["coef"], ref["intercept"])
    got = np.stack([with_intercept(a["coef"], a["intercept"])
                    for a in answers])
    coef_gap = float(np.max(np.linalg.norm(got - want, axis=1))
                     / np.linalg.norm(want))
    # identical models (a deterministic fit repeats itself) are judged once
    distinct, index = np.unique(got, axis=0, return_inverse=True)
    theirs = np.array([a["objective"] for a in answers])
    ours = ref["problem"].objective_of(distinct[:, :-1], distinct[:, -1])
    ours = np.asarray(ours)[np.ravel(index)]
    objective_gap = float(np.max(np.abs(theirs - ours) / np.abs(ours)))
    out = {}
    for name, value in (("coef_gap", coef_gap),
                        ("objective_gap", objective_gap)):
        limit = float(limits[name])
        out[name] = {"value": value, "limit": limit,
                     "ok": bool(np.isfinite(value) and value <= limit)}
    return out
