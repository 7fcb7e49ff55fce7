"""Benchmark driver: one section per paper table/figure + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--trajectory]

``--full`` runs the paper's exact scale (100 OSS / 2,000 requests / 100
trials) and adds the full-scale temporal scenario sweep; the default is a
faster configuration with identical structure.  ``--trajectory`` skips
the benchmarks and renders the BENCH_sched.json history instead: the
phase-time/p99 delta table, the scheduling-throughput table
(``engine_req_s`` / ``kernel_req_s`` / ``kernel_batch_req_s`` /
the sort-policy pairs ``kernel_batch_req_s_{mlml,nltr}`` vs their
same-policy engine twins ``engine_req_s_{mlml,nltr}`` /
``sharded_req_s_{d}d`` / the §14 batched-pipeline e2e pairs
``e2e_req_s_{kernel,jax}`` vs their same-backend sequential
(lax.map-halo) twins ``e2e_seq_req_s_{kernel,jax}``, flagging runs
where a kernel path fell behind its engine twin or a batched e2e fell
behind its sequential twin) and a two-panel figure.  Each point also
records the prep/sched/post stage wall times
(``prep_s``/``sched_s_{kernel,jax}``/``post_s``) of the batched trial
pipeline at the 64-client short-stream instance.  BENCH_sched.json is the
IN-REPO file at the repo root (``sched_perf.BENCH_PATH``), one point
per git sha (each point stamps ``git_dirty``) — re-running on the same
commit replaces the point.  The roofline section formats whatever
``dryrun_results.json`` the dry-run has produced so far.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Kernel throughput series gated by --check-regression (req/s — higher
# is better).  Sharded series join dynamically: their keys carry the
# device count (sharded_req_s_{d}d), so matching keys across two points
# automatically compares same-device-count runs only.
REGRESSION_KEYS = (
    "kernel_req_s", "kernel_batch_req_s",
    "kernel_batch_req_s_mlml", "kernel_batch_req_s_nltr",
    "kernel_batch_req_s_per_client", "e2e_req_s_kernel",
    "tuned_kernel_req_s", "tuned_kernel_req_s_mlml",
    "tuned_kernel_req_s_nltr", "tuned_kernel_req_s_per_client_4c",
)


def check_regression(path: str | None = None,
                     tolerance: float = 0.3) -> int:
    """Gate the LATEST bench point against the most recent earlier CLEAN
    point (``git_dirty`` stamped false): exit nonzero when any kernel
    throughput series fell more than ``tolerance`` below the baseline.

    Dirty-tree points never serve as the baseline — their numbers were
    measured on uncommitted code.  With fewer than two comparable points
    the gate passes trivially (a fresh fork has no history to regress
    against)."""
    from benchmarks import sched_perf
    path = path or sched_perf.BENCH_PATH
    if not os.path.exists(path):
        print(f"[check-regression] {path} not found — pass (no history)")
        return 0
    try:
        with open(path) as f:
            history = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        print(f"[check-regression] {path} unreadable ({e}) — pass")
        return 0
    if not isinstance(history, list):
        history = [history]
    history = [pt for pt in history if isinstance(pt, dict)]
    if len(history) < 2:
        print(f"[check-regression] {len(history)} point(s) — pass "
              "(need a baseline and a candidate)")
        return 0
    latest = history[-1]
    base = next((pt for pt in reversed(history[:-1])
                 if pt.get("git_dirty") is False), None)
    if base is None:
        print("[check-regression] no earlier clean (git_dirty=false) "
              "baseline point — pass")
        return 0
    keys = [k for k in REGRESSION_KEYS
            if isinstance(latest.get(k), (int, float))
            and isinstance(base.get(k), (int, float))]
    keys += sorted(k for k in latest
                   if k.startswith("sharded_req_s_")
                   and isinstance(latest.get(k), (int, float))
                   and isinstance(base.get(k), (int, float)))
    if not keys:
        print("[check-regression] no comparable throughput series — pass")
        return 0
    b_sha = str(base.get("git_sha", "?"))[:12]
    l_sha = str(latest.get("git_sha", "?"))[:12]
    print(f"[check-regression] latest ({l_sha}) vs clean baseline "
          f"({b_sha}), tolerance {tolerance:.0%}")
    print(f"{'series':>36s} {'baseline':>12s} {'latest':>12s} "
          f"{'ratio':>7s}")
    failures = []
    for k in keys:
        ratio = latest[k] / max(base[k], 1e-12)
        flag = "" if ratio >= 1.0 - tolerance else "  <-- REGRESSED"
        print(f"{k:>36s} {base[k]:12.0f} {latest[k]:12.0f} "
              f"{ratio:7.2f}{flag}")
        if flag:
            failures.append(k)
    if failures:
        print(f"[check-regression] FAIL: {len(failures)} series past "
              f"tolerance: {', '.join(failures)}")
        return 1
    print(f"[check-regression] ok ({len(keys)} series)")
    return 0


def main() -> None:
    if "--check-regression" in sys.argv:
        sys.exit(check_regression())
    if "--trajectory" in sys.argv:
        from benchmarks import sched_perf
        sched_perf.trajectory(sched_perf.BENCH_PATH)
        return
    full = "--full" in sys.argv
    t0 = time.time()
    print("=" * 72)
    print("repro benchmarks — log-assisted straggler-aware I/O scheduling")
    print("=" * 72)

    from benchmarks import paper_figs
    paper_figs.run_all(full=full)

    from benchmarks import sched_perf
    sched_perf.run_all()
    # one perf-trajectory point per run, appended to the IN-REPO
    # BENCH_sched.json (repo-root anchored, deduped by git sha — a
    # re-run on the same commit replaces that commit's point)
    sched_perf.emit_bench_point(sched_perf.BENCH_PATH)

    from benchmarks import kernels_bench
    kernels_bench.run_all()

    from benchmarks import roofline
    import os
    path = "dryrun_results.json"
    if os.path.exists(path):
        roofline.summary(path)
        roofline.table(path, "16x16")
        roofline.hillclimb_candidates(path)
    else:
        print("\n[roofline] dryrun_results.json not found — skip "
              "(run python -m repro.launch.dryrun)")

    print(f"\n[benchmarks] total wall time {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
