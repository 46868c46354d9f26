"""The yardstick's answers and the comparison that decides ``correct``.

Nothing here imports the engine.  A configuration's reference composes
:func:`lookup`, :func:`grouped` and :func:`exact_segment_sum`; the harness
turns each answer the engine served into the same form, a dictionary
``{group key: sum}`` (``{"all": sum}`` for a scalar), and :func:`compare`
holds every served answer to its template's reference.

The configurations state exact answers, so every limit is 0: a served
answer either equals the reference group for group, or the run is not
correct.
"""
from __future__ import annotations

import numpy as np

#: limits of the numbers compared; exact answers have limit 0
LIMITS = {"wrong_answers": 0, "max_abs_diff": 0, "unanswered": 0}


def exact_segment_sum(values: np.ndarray, gid: np.ndarray,
                      n_groups: int) -> np.ndarray:
    """Per-group sums in int64: exact for integer values."""
    order = np.argsort(gid, kind="stable")
    g = np.asarray(gid)[order]
    out = np.zeros(n_groups, dtype=np.int64)
    if len(g):
        starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
        out[g[starts]] = np.add.reduceat(
            np.asarray(values, dtype=np.int64)[order], starts)
    return out


def lookup(dim_keys: np.ndarray, fact_keys: np.ndarray):
    """Row of each fact key in the dimension, and whether the key is there."""
    order = np.argsort(dim_keys, kind="stable")
    pos = np.clip(np.searchsorted(dim_keys[order], fact_keys), 0,
                  len(dim_keys) - 1)
    row = order[pos]
    return row, dim_keys[row] == fact_keys


def grouped(keys: np.ndarray, values: np.ndarray, segment_sum) -> dict:
    """``{key: sum of values}`` over the distinct keys."""
    uniq, gid = np.unique(keys, return_inverse=True)
    sums = segment_sum(values, gid.reshape(-1), len(uniq))
    return {int(k): float(s) for k, s in zip(uniq.tolist(),
                                            np.asarray(sums).tolist())}


def scalar(values: np.ndarray, segment_sum) -> dict:
    """``{"all": sum of values}``."""
    return {"all": float(np.asarray(
        segment_sum(values, np.zeros(len(values), np.int64), 1))[0])}


def answer_diff(got: dict, want: dict) -> float:
    """Largest gap between two answers over the union of their groups; a
    group that one side lacks counts with its whole value, or as a gap of 1
    where that value is 0."""
    gap = 0.0
    for k in set(got) | set(want):
        if k not in got or k not in want:
            v = abs(got.get(k, want.get(k, 0.0)))
            gap = max(gap, v if v else 1.0)
        else:
            gap = max(gap, abs(got[k] - want[k]))
    return gap


def compare(served, references: dict, unanswered: int) -> dict:
    """Hold every served answer to its template's reference.

    ``served`` is a list of ``(template, answer)``; ``references`` maps a
    template to its reference answer; ``unanswered`` counts the queries
    submitted in the window that returned no answer.  Returns each number
    compared with its limit, ``{name: {"value": v, "limit": l}}``.
    """
    diffs = [answer_diff(ans, references[t]) for t, ans in served]
    values = {"wrong_answers": sum(d != 0 for d in diffs),
              "max_abs_diff": max(diffs, default=0.0),
              "unanswered": unanswered}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def is_correct(checks: dict, n_served: int) -> bool:
    return n_served > 0 and all(c["value"] <= c["limit"]
                                for c in checks.values())
