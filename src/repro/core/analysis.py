"""Balance / straggler / latency metrics over scheduling outcomes.

Covers the paper's §4 figures (load balance, Fig. 18 straggler avoidance,
probe overhead) plus the temporal-model metrics (DESIGN.md
§Temporal-model): latency percentiles, makespan, straggler-hit-over-time
and slowdown-vs-baseline summaries.

All functions take numpy-or-jnp arrays with an optional leading trial axis
and return plain floats / numpy arrays, so benchmarks can print CSV without
touching device buffers.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from repro.core import policy_core


def _np(a) -> np.ndarray:
    return np.asarray(a)


def load_balance_stats(server_loads) -> Dict[str, float]:
    """max / min / mean / std / CV / Jain fairness of per-server loads.

    ``server_loads``: (T, M) or (M,).  Trial axis is averaged the way the
    paper does (average load of each OSS over 100 runs, then statistics).
    """
    loads = _np(server_loads).astype(np.float64)
    if loads.ndim == 2:
        loads = loads.mean(axis=0)
    mean = float(loads.mean())
    std = float(loads.std())
    jain = float(loads.sum() ** 2 / (len(loads) * (loads ** 2).sum()))
    return {
        "max": float(loads.max()),
        "min": float(loads.min()),
        "mean": mean,
        "std": std,
        "cv": std / mean if mean else float("inf"),
        "jain": jain,
        "spread": float(loads.max() - loads.min()),
    }


def mean_server_loads(server_loads) -> np.ndarray:
    """(M,) per-OSS load averaged over trials (Figs. 12-17 y-axis)."""
    loads = _np(server_loads).astype(np.float64)
    return loads.mean(axis=0) if loads.ndim == 2 else loads


def fig18_curve(server_loads, n_assigned, n_bins: int = 30,
                lo: Optional[float] = None,
                hi: Optional[float] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Paper Fig. 18: x = possible post-scheduling load; y = the MAX number
    of requests landed on any server having that load.

    Accepts (T, M) arrays; buckets are shared across trials so policies can
    be overlaid on one axis.
    """
    loads = _np(server_loads).astype(np.float64).reshape(-1)
    reqs = _np(n_assigned).astype(np.float64).reshape(-1)
    lo = float(loads.min()) if lo is None else lo
    hi = float(loads.max()) if hi is None else hi
    if hi <= lo:
        hi = lo + 1.0
    edges = np.linspace(lo, hi, n_bins + 1)
    centers = 0.5 * (edges[:-1] + edges[1:])
    which = np.clip(np.digitize(loads, edges) - 1, 0, n_bins - 1)
    ymax = np.zeros(n_bins)
    np.maximum.at(ymax, which, reqs)
    return centers, ymax


def straggler_summary(result) -> Dict[str, float]:
    """Straggler-avoidance metrics from a :class:`TrialResult`."""
    hits = _np(result.straggler_hits).astype(np.float64)
    loads = _np(result.server_loads).astype(np.float64)
    mask = _np(result.straggler_mask).astype(bool)
    n_req = _np(result.chosen).shape[-1]
    if loads.ndim == 1:
        loads, mask = loads[None], mask[None]
    strag_growth = []
    for t in range(loads.shape[0]):
        init = _np(result.init_loads)[t] if _np(result.init_loads).ndim == 2 \
            else _np(result.init_loads)
        if mask[t].any():
            strag_growth.append(float((loads[t] - init)[mask[t]].mean()))
    return {
        "mean_straggler_hits": float(hits.mean()),
        "hit_fraction": float(hits.mean()) / n_req,
        "mean_bytes_added_to_stragglers_mb":
            float(np.mean(strag_growth)) if strag_growth else 0.0,
        "max_load": float(loads.max(axis=1).mean()),
    }


def latency_stats(latencies) -> Dict[str, float]:
    """p50/p95/p99/mean/max of per-request estimated completion latencies.

    ``latencies``: (R,) or (T, R) seconds (temporal model).  Percentiles
    pool all trials' requests — the paper-scale question is "what does the
    99th-percentile request see", not "the 99th-percentile trial".

    Two p99 definitions coexist (pinned against a hand-computed example
    in tests/test_simulate.py):

    * ``p99`` — ``np.percentile``'s LINEAR-interpolated quantile (a
      weighted average of the two order statistics straddling rank
      0.99·(n-1)+1), kept for the figures so existing plots don't move;
    * ``p99_nearest`` — the NEAREST-RANK definition (the smallest value
      with at least ``ceil(0.99·n)`` values ≤ it), computed by the SAME
      `policy_core.nearest_rank_p99` f32 value bisection the sweep
      runs on its merged latency block (DESIGN.md §14) — the
      host-side number that matches ``MET_P99`` / ``SweepMerge.p99``
      bit-for-bit.  Nearest-rank is always an actual observed latency;
      linear interpolation generally is not, so the two differ whenever
      0.99·n falls between order statistics.
    """
    lat = _np(latencies).astype(np.float64).reshape(-1)
    lat32 = lat.astype(np.float32)
    p99_nr = policy_core.nearest_rank_p99(
        lat32, np.ones(lat32.shape, bool), xp=np)
    return {
        "p50": float(np.percentile(lat, 50)),
        "p95": float(np.percentile(lat, 95)),
        "p99": float(np.percentile(lat, 99)),
        "p99_nearest": float(np.asarray(p99_nr).reshape(-1)[0]),
        "mean": float(lat.mean()),
        "max": float(lat.max()),
    }


def makespan(result) -> float:
    """Mean (over trials) I/O-phase makespan: the latest estimated
    completion time of any request (``TrialResult.phase_time``)."""
    return float(_np(result.phase_time).astype(np.float64).mean())


def latency_cdf(latencies, n_points: int = 64) -> Tuple[np.ndarray, np.ndarray]:
    """Empirical CDF of request latencies: (latency grid, P[lat <= x])."""
    lat = np.sort(_np(latencies).astype(np.float64).reshape(-1))
    xs = np.linspace(lat[0], lat[-1] if lat[-1] > lat[0] else lat[0] + 1.0,
                     n_points)
    ys = np.searchsorted(lat, xs, side="right") / len(lat)
    return xs, ys


def straggler_hits_over_time(chosen, straggler_mask,
                             window_size: int) -> np.ndarray:
    """Fraction of each window's requests landing on stragglers, averaged
    over trials — shows onset/recovery tracking under temporal scenarios.

    ``chosen``: (T, R) or (R,); ``straggler_mask``: (T, M) or (M,).
    """
    ch = _np(chosen)
    mask = _np(straggler_mask).astype(bool)
    if ch.ndim == 1:
        ch = ch[None]
    if mask.ndim == 1:                     # shared mask across trials
        mask = np.broadcast_to(mask, (ch.shape[0], mask.shape[0]))
    t, r = ch.shape
    n_win = -(-r // window_size)
    pad = n_win * window_size - r
    hit = np.take_along_axis(mask, ch, axis=1).astype(np.float64)
    if pad:
        hit = np.concatenate([hit, np.full((t, pad), np.nan)], axis=1)
    per_win = np.nanmean(hit.reshape(t, n_win, window_size), axis=2)
    return per_win.mean(axis=0)


def slowdown_vs_baseline(results: Dict[str, object],
                         baseline: str = "rr") -> Dict[str, Dict[str, float]]:
    """Per-policy p99-latency and makespan ratios vs a baseline policy
    (values < 1 mean the policy beats the baseline)."""
    base_p99 = latency_stats(results[baseline].latencies)["p99"]
    base_mk = makespan(results[baseline])
    out = {}
    for name, res in results.items():
        out[name] = {
            "p99_vs_" + baseline: latency_stats(res.latencies)["p99"]
            / max(base_p99, 1e-12),
            "makespan_vs_" + baseline: makespan(res) / max(base_mk, 1e-12),
        }
    return out


def probe_overhead(results: Dict[str, object], n_requests: int) -> Dict[str, float]:
    """Probe messages per request per policy (the cost the log removes)."""
    return {name: float(_np(r.probe_msgs).mean()) / n_requests
            for name, r in results.items()}


def ascii_plot(ys: np.ndarray, width: int = 72, height: int = 12,
               label: str = "") -> str:
    """Tiny dependency-free line plot for benchmark stdout."""
    ys = _np(ys).astype(np.float64)
    if len(ys) > width:
        idx = np.linspace(0, len(ys) - 1, width).astype(int)
        ys = ys[idx]
    lo, hi = float(ys.min()), float(ys.max())
    span = (hi - lo) or 1.0
    rows = []
    q = np.clip(((ys - lo) / span * (height - 1)).round().astype(int), 0,
                height - 1)
    for r in range(height - 1, -1, -1):
        line = "".join("█" if q[c] >= r else " " for c in range(len(ys)))
        rows.append(f"{(lo + span * r / (height - 1)):10.1f} |{line}")
    rows.append(" " * 11 + "+" + "-" * len(ys))
    if label:
        rows.insert(0, f"  {label}  [min={lo:.2f} max={hi:.2f}]")
    return "\n".join(rows)
