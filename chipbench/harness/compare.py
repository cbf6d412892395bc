"""The comparison that decides ``correct``.

The served records of every task decided in warm-up, in the window and in
the drain are held against the plain reference (``harness.reference``) run
over the same tasks. Each number compared has its own limit, set in
``LIMITS`` from the readings that ``PERF.md`` lists (sound runs of the
program over a dozen seeds or more, and the float32 control):

- ``missing``: tasks due that have no served record, or records of tasks
  that were never sent. Exact: limit 0.
- ``decisions_differ``: tasks whose target, predicted cold start or
  feasibility differs from the reference. Exact: limit 0.
- ``pred_rel_err``: the largest relative gap of the predicted latency or
  cost, over tasks whose target agrees.
- ``outcome_cold_differ``: tasks whose executed cold start differs. Exact:
  limit 0.
- ``outcome_rel_err``: the largest relative gap of the executed latency or
  cost, over all tasks.
"""

from __future__ import annotations

import numpy as np

LIMITS = {
    "missing": 0,
    "decisions_differ": 0,
    "pred_rel_err": 1e-10,
    "outcome_cold_differ": 0,
    "outcome_rel_err": 1e-10,
}


NOT_FINITE = 1e300   # the gap reported where one side is not a finite number


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    """Largest ``|a - b| / |b|`` (``|b|`` at least 1e-300, so a gap to an
    exact zero reads huge); ``NOT_FINITE`` where the two sides disagree on
    being finite. Always a finite number, so the result line stays JSON."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    fa, fb = np.isfinite(a), np.isfinite(b)
    if np.any(fa != fb) or np.any(fa & fb & np.isnan(a)):
        return NOT_FINITE
    both = fa & fb
    err = np.abs(a[both] - b[both]) / np.maximum(np.abs(b[both]), 1e-300)
    return float(min(np.max(err), NOT_FINITE)) if err.size else 0.0


def readings(served: dict, ref: dict, names: list[str]) -> dict:
    """The compared numbers of a served stream (``sut.records`` form, or a
    reference run put in the program's place) against the reference.
    ``served["task_idx"]`` names which stream positions were served."""
    n = ref["code"].shape[0]
    idx = np.asarray(served["task_idx"], np.int64)
    ok = (idx >= 0) & (idx < n)
    served_once = np.unique(idx[ok]).shape[0]
    # tasks with no record, plus records of no task or of a task twice
    missing = (n - served_once) + (idx.shape[0] - served_once)
    idx = idx[ok]
    ref_t = np.asarray(names, dtype=object)[ref["code"][idx]]
    srv_t = np.asarray(served["target"], dtype=object)[ok]
    same_t = srv_t == ref_t
    differ = (~same_t) \
        | (np.asarray(served["pred_cold"])[ok] != ref["pred_cold"][idx]) \
        | (np.asarray(served["feasible"])[ok] != ref["feasible"][idx])
    pred = max(
        _rel(np.asarray(served["pred_latency"])[ok][same_t],
             ref["pred_latency"][idx][same_t]),
        _rel(np.asarray(served["pred_cost"])[ok][same_t],
             ref["pred_cost"][idx][same_t]))
    out = max(_rel(np.asarray(served["actual_latency"])[ok],
                   ref["actual_latency"][idx]),
              _rel(np.asarray(served["actual_cost"])[ok],
                   ref["actual_cost"][idx]))
    cold = np.asarray(served["actual_cold"])[ok] != ref["actual_cold"][idx]
    return {"missing": int(missing),
            "decisions_differ": int(np.count_nonzero(differ)),
            "pred_rel_err": pred,
            "outcome_cold_differ": int(np.count_nonzero(cold)),
            "outcome_rel_err": out}


def verdict(numbers: dict, limits: dict = LIMITS) -> tuple[bool, list]:
    """``(correct, [[name, number, limit], ...])``: correct when every
    number is at or under its limit (a NaN fails)."""
    rows = [[k, numbers[k], limits[k]] for k in limits]
    return all(v <= lim for _, v, lim in rows), rows


def as_served(ref: dict, names: list[str]) -> dict:
    """A reference run in the ``sut.records`` form (the control)."""
    n = ref["code"].shape[0]
    return {"task_idx": np.arange(n),
            "target": list(np.asarray(names, dtype=object)[ref["code"]]),
            **{k: ref[k] for k in ("pred_latency", "pred_cost", "pred_cold",
                                   "feasible", "actual_latency",
                                   "actual_cost", "actual_cold")}}
