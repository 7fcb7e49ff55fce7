"""The comparison that decides ``correct``.

Two numbers, each against a limit of the cell's own (``limits/<cell>.json``):

* ``wrong_answers`` — the share of answers that the plain reference does
  not give: every request's server (judged by a teacher-forced replay, so
  the reference decides each step from the log as the program's own
  earlier decisions left it), and every integer field (requests per
  server, probes, redirects, straggler hits, the straggler mask, the
  effective window).
* ``value_gap`` — the widest gap between a float field of the program and
  of the reference, as a share of that field's largest magnitude in the
  reference: initial loads (prep), final server loads, request latencies,
  per-window load snapshots (the cross-client mean under per_client) and
  the makespan (post, merge).
"""

from __future__ import annotations

import numpy as np

FLOAT_FIELDS = ("init_loads", "server_loads", "latencies", "window_loads",
                "phase_time")
INT_FIELDS = ("n_assigned", "probe_msgs", "straggler_hits", "redirected",
              "straggler_mask", "window_size_eff")
NUMBERS = ("wrong_answers", "value_gap")
NEVER = 1e300   # the gap of a field that is not finite, or not there


def malformed(prog: dict, shapes: dict) -> str:
    """Why a program output does not have the reference's fields and
    shapes ('' when it does)."""
    for f in ("chosen",) + FLOAT_FIELDS + INT_FIELDS:
        if f not in prog:
            return f"missing field {f}"
        if tuple(np.shape(prog[f])) != tuple(shapes[f]):
            return (f"field {f} has shape {tuple(np.shape(prog[f]))}, "
                    f"expected {tuple(shapes[f])}")
    return ""


def compare(prog: dict, ref: dict) -> dict:
    """Counts of one sweep: ``wrong``/``answers`` and the widest float
    gap, with the field it came from."""
    wrong = int(np.sum(np.asarray(prog["chosen"]) != ref["mine"]))
    answers = int(np.size(ref["mine"]))
    for f in INT_FIELDS:
        p, r = np.asarray(prog[f]), np.asarray(ref[f])
        wrong += int(np.sum(p.astype(np.int64) != r.astype(np.int64)))
        answers += int(r.size)
    gaps = {}
    for f in FLOAT_FIELDS:
        p = np.asarray(prog[f], np.float64)
        r = np.asarray(ref[f], np.float64)
        scale = max(float(np.max(np.abs(r))), 1e-30)
        g = float(np.max(np.abs(p - r))) / scale
        gaps[f] = g if np.isfinite(g) else NEVER
    where = max(gaps, key=gaps.get)
    return {"wrong": wrong, "answers": answers, "value_gap": gaps[where],
            "gap_field": where}


def combine(parts: list) -> dict:
    """The compared numbers over every checked sweep."""
    wrong = sum(p["wrong"] for p in parts)
    answers = sum(p["answers"] for p in parts)
    worst = max(parts, key=lambda p: p["value_gap"])
    return {"wrong_answers": wrong / max(answers, 1),
            "value_gap": worst["value_gap"],
            "wrong": wrong, "answers": answers,
            "gap_field": worst["gap_field"]}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[n] <= limits[n] for n in NUMBERS)
