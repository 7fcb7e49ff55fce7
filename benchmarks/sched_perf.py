"""§Perf hillclimb C — the paper's own technique: I/O phase completion time.

The metric is the *synchronous I/O phase time* on the queueing cluster
(the quantity the paper's load balance ultimately serves, Fig. 1): 24
servers at 200 MB/s, one slow-rate straggler (8x) with 800 MB of foreign
queue, one half-loaded server; 120 files x 16 MB written through the
client.  Each iteration follows hypothesis -> change -> measure; results
are recorded in EXPERIMENTS.md §Perf.

Temporal extension (DESIGN.md §Temporal-model): ``scenario_ranking``
ranks every policy by p50/p95/p99 latency and makespan under each
scenario of the library (jitted ``run_trials`` sweep), and
``transient_latency_cdf`` prints the latency CDF under a transient
straggler trace.  ``emit_bench_point`` appends one JSON point per run to
``BENCH_sched.json`` for the perf trajectory.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

import numpy as np

from repro.core.policies import PolicyConfig
from repro.io import IOClient, IOClientConfig, SimulatedCluster
from repro.io.striping import MB

# BENCH_sched.json lives at the REPO ROOT regardless of cwd — the
# trajectory is one in-repo history, not a scatter of per-cwd files.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(_REPO_ROOT, "BENCH_sched.json")


def _git_sha() -> Optional[str]:
    """HEAD sha for bench-point dedup (one point per commit); None when
    git is unavailable (e.g. a source tarball)."""
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
                             capture_output=True, text=True, timeout=10)
    except OSError:
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def _git_dirty() -> Optional[bool]:
    """True when the working tree differs from HEAD — stamped alongside
    ``git_sha`` so the sha-keyed dedupe can't silently merge points
    measured on different trees; None when git is unavailable."""
    try:
        out = subprocess.run(["git", "status", "--porcelain"],
                             cwd=_REPO_ROOT, capture_output=True,
                             text=True, timeout=10)
    except OSError:
        return None
    if out.returncode != 0:
        return None
    return bool(out.stdout.strip())


def _stamp_git(point: dict) -> dict:
    """Stamp ``git_sha``/``git_dirty`` onto a bench point, warning LOUDLY
    when the tree is dirty — a dirty-tree number silently entering the
    trajectory is exactly how a regression hides behind an uncommitted
    tweak.  Returns the point for chaining."""
    sha = _git_sha()
    if sha:
        point["git_sha"] = sha
        dirty = _git_dirty()
        if dirty is not None:
            point["git_dirty"] = dirty
            if dirty:
                print("=" * 72, file=sys.stderr)
                print("[sched_perf] WARNING: working tree is DIRTY — this "
                      "bench point is\n[sched_perf] stamped git_dirty=true "
                      "and will NOT serve as a regression baseline.\n"
                      "[sched_perf] Commit first for a clean trajectory "
                      f"point (HEAD {sha[:12]}).", file=sys.stderr)
                print("=" * 72, file=sys.stderr)
    return point


def device_info() -> Dict[str, object]:
    """The device JAX runs on, as every device-measured result names it:
    platform, device kind and device count."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": jax.device_count()}


def _device_tag() -> str:
    i = device_info()
    return f"[{i['platform']} {i['device_kind']} x{i['device_count']}]"


# Memoized: run_all prints a full iteration table and emit_bench_point
# re-reads three of the same cells — don't pay for the simulation twice.
@functools.lru_cache(maxsize=None)
def phase_time(policy: str, threshold: float = 4.0,
               stripe_mb: float = 4.0, n_files: int = 120,
               file_mb: float = 16.0, lam: float = 32.0,
               know_loads: bool = True, warm_probs: bool = False,
               refresh: bool = False, seed: int = 3) -> Dict[str, float]:
    sim = SimulatedCluster(24, base_rate_mb_s=200.0, seed=seed)
    sim.make_straggler(1, 8.0)
    sim.add_external_load(1, 800.0)
    sim.add_external_load(5, 400.0)
    cli = IOClient(sim, IOClientConfig(
        policy=PolicyConfig(name=policy, threshold=threshold),
        stripe_size=int(stripe_mb * MB), lam_mb=lam,
        refresh_probs=refresh))
    if know_loads:
        for s in range(sim.n_servers):
            cli.log.loads[s] = sim.queued_mb(s)
        if warm_probs:
            cli.log.absorb_loads()  # p_i ∝ e^{-l_i/λ}: sorts become load-aware
    for f in range(n_files):
        cli.write_file(f, size_mb=file_mb)
    t = cli.flush()
    return {"phase_s": t,
            "straggler_hits": sim.servers[1].n_requests,
            "probes": cli.probe_messages,
            "redirect_entries": sum(len(r) for r in sim.redirects)}


def ideal_phase_time() -> float:
    """Roofline for this workload: total bytes spread over the 22 clean
    servers (a perfect scheduler avoids both the straggler's 32 s foreign
    queue and server 5's 2 s queue)."""
    total_mb = 120 * 16.0
    return total_mb / (22 * 200.0)


# ---------------------------------------------------------------------------
# Temporal scenarios (DESIGN.md §Temporal-model): latency / makespan ranking
# ---------------------------------------------------------------------------

# single source of truth: the simulator's scenario/policy libraries
from repro.core.simulate import (SCENARIOS as SWEEP_SCENARIOS,  # noqa: E402
                                 SWEEP_POLICIES)


def _sweep_cfg(n_trials: int = 25):
    from repro.core.simulate import SimConfig
    return SimConfig(n_servers=24, n_requests=480, n_trials=n_trials,
                     window_size=60)


# One seed-0 sweep per (scenarios, policies, trials) per process —
# scenario_ranking, transient_latency_cdf and emit_bench_point overlap.
_SWEEP_CACHE: Dict[tuple, dict] = {}


def _scenario_sweep(scenario_names: tuple, policy_names: tuple,
                    n_trials: int) -> dict:
    key = (scenario_names, policy_names, n_trials)
    if key not in _SWEEP_CACHE:
        from repro.core import simulate
        _SWEEP_CACHE[key] = simulate.run_scenario_eval(
            seed=0, cfg=_sweep_cfg(n_trials),
            scenario_names=scenario_names, policy_names=policy_names)
    return _SWEEP_CACHE[key]


def _transient_results(n_trials: int) -> dict:
    """{policy: TrialResult} under the transient trace, reusing the full
    ranking sweep when it has already run this process."""
    full = (SWEEP_SCENARIOS, SWEEP_POLICIES, n_trials)
    if full in _SWEEP_CACHE:
        row = _SWEEP_CACHE[full]["transient"]
        return {p: row[p] for p in ("rr", "trh", "ect")}
    return _scenario_sweep(("transient",), ("rr", "trh", "ect"),
                           n_trials)["transient"]


def _median_time(run, reps: int):
    """Median wall time of ``reps`` timed calls, warmup (compile) run
    excluded — single-shot numbers flipped kernel/engine winners between
    benchmark runs, so every tracked throughput is a median.  Returns
    ``(median_s, warmup_result)`` so callers needing the outputs (e.g.
    for bit-exactness checks) don't pay for an extra untimed run."""
    import jax
    warm = run()
    jax.block_until_ready(warm)                # compile + warm, untimed
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.time()
        jax.block_until_ready(run())
        times.append(time.time() - t0)
    return float(np.median(times)), warm


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def kernel_vs_engine_throughput(n_servers: int = 100,
                                n_requests: int = 2000,
                                window_size: int = 100,
                                reps: int = 3) -> Dict[str, float]:
    """Scheduling throughput (requests scheduled/s): the Pallas temporal
    kernel (whole stream = ONE pallas_call, packed log tensor in VMEM)
    vs the lax.scan JAX engine, on the 100-OSS transient scenario.

    Reported wall times are the MEDIAN of ``reps`` runs (warmup
    excluded); the repeat count rides along in the emitted bench point.
    On CPU the kernel runs in interpret mode, so the absolute numbers are
    a lower bound — the structural point is that both backends schedule
    the SAME trace from the same decision table (bit-exact for ect,
    asserted here) and the kernel-backend wall time is tracked per run in
    BENCH_sched.json.
    """
    import jax
    from repro.core import engine, simulate, statlog
    from repro.core.simulate import ScenarioConfig, SimConfig

    cfg = SimConfig(n_servers=n_servers, n_requests=n_requests, n_trials=1,
                    window_size=window_size,
                    scenario=ScenarioConfig(name="transient"))
    scn = cfg.scenario
    key = jax.random.key(0)
    work = simulate.sample_workload(key, cfg)
    trace = simulate.make_trace(jax.random.fold_in(key, 1), cfg, scn)
    window_dt = simulate.resolve_window_dt(cfg, scn)
    log_cfg = simulate.default_log_cfg(cfg)
    pol = PolicyConfig(name="ect", threshold=0.05)
    state = statlog.init_state(log_cfg, rates=trace.rates[0])

    out: Dict[str, float] = {"n_servers": n_servers,
                             "n_requests": n_requests, "reps": reps}
    chosen = {}
    for backend in ("jax", "kernel"):
        run = functools.partial(
            engine.run_stream_jit, state, work, key, policy=pol,
            log_cfg=log_cfg, window_size=window_size, trace=trace,
            window_dt=window_dt, backend=backend)
        dt, warm = _median_time(lambda: run().chosen, reps)
        chosen[backend] = np.asarray(warm)
        out[f"{backend}_s"] = dt
        out[f"{backend}_req_s"] = n_requests / dt
    out["bit_exact"] = bool((chosen["jax"] == chosen["kernel"]).all())
    print(f"\n== kernel vs JAX engine scheduling throughput "
          f"({n_servers} OSS x {n_requests} reqs, transient trace, "
          f"median of {reps}) == {_device_tag()}")
    print(f"{'backend':>8s} {'wall_s':>8s} {'req/s':>10s}")
    for b in ("jax", "kernel"):
        print(f"{b:>8s} {out[f'{b}_s']:8.3f} {out[f'{b}_req_s']:10.0f}")
    print(f"  decisions bit-exact across backends: {out['bit_exact']}"
          + ("" if out["bit_exact"] else "  <-- DIVERGED"))
    return out


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def kernel_batch_throughput(n_servers: int = 100, n_requests: int = 2000,
                            window_size: int = 100, n_trials: int = 100,
                            reps: int = 3, policy: str = "ect",
                            threshold: float = 0.05,
                            check_bit_exact: bool = True,
                            measure_engine: bool = False
                            ) -> Dict[str, float]:
    """Trial-grid kernel throughput (DESIGN.md §9): the WHOLE Monte-Carlo
    sweep — ``n_trials`` independent transient-scenario streams — as ONE
    pallas_call (`simulate.run_trials(backend='kernel')`), vs. the same
    sweep mapped trial-by-trial through the sequential kernel path.

    ``policy`` selects the in-kernel decision rule — since the in-VMEM
    sorts (DESIGN.md §10) this includes the sort-based ``mlml``/``nltr``,
    which now run the §13 permutation-apply fast path (one all-pairs
    rank + a constant number of permutation applies per window, tracked
    per run in BENCH_sched.json as ``kernel_batch_req_s_<policy>``).

    ``kernel_batch_req_s`` is aggregate (trials x requests) / median
    wall seconds; ``batch_bit_exact`` asserts every per-trial decision,
    latency and load of the grid kernel equals the ``lax.map`` path —
    the tentpole contract of the trial-grid form.  ``measure_engine``
    also times the SAME sweep through the vmapped jax engine
    (``engine_batch_req_s``) — the same-policy engine twin the
    trajectory's behind-engine flag compares against."""
    import jax
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig

    cfg = SimConfig(n_servers=n_servers, n_requests=n_requests,
                    n_trials=n_trials, window_size=window_size,
                    backend="kernel",
                    scenario=ScenarioConfig(name="transient"))
    log_cfg = simulate.default_log_cfg(cfg)
    rng = "lcg" if policy in ("trh", "nltr", "two_choice") else "jax"
    pol = PolicyConfig(name=policy, threshold=threshold, rng=rng)
    key = jax.random.key(0)

    # time the whole TrialResult and keep the warm output — the
    # bit-exactness check below reuses it instead of paying for one
    # more full sweep
    dt, batch = _median_time(
        lambda: simulate.run_trials(key, cfg, pol, log_cfg), reps)
    out: Dict[str, float] = {
        "n_servers": n_servers, "n_requests": n_requests,
        "n_trials": n_trials, "reps": reps, "policy": policy,
        "batch_s": dt,
        "kernel_batch_req_s": n_trials * n_requests / dt,
    }
    if measure_engine:
        ecfg = SimConfig(n_servers=n_servers, n_requests=n_requests,
                         n_trials=n_trials, window_size=window_size,
                         backend="jax",
                         scenario=ScenarioConfig(name="transient"))
        elog = simulate.default_log_cfg(ecfg)
        edt, _ = _median_time(
            lambda: simulate.run_trials(key, ecfg, pol, elog), reps)
        out["engine_batch_s"] = edt
        out["engine_batch_req_s"] = n_trials * n_requests / edt
    if check_bit_exact:
        keys = jax.random.split(key, n_trials)
        seq = jax.jit(lambda ks: jax.lax.map(
            lambda k: simulate.run_one_trial(k, cfg, pol, log_cfg), ks)
        )(keys)
        out["batch_bit_exact"] = bool(
            (np.asarray(batch.chosen) == np.asarray(seq.chosen)).all()
            and (np.asarray(batch.latencies)
                 == np.asarray(seq.latencies)).all()
            and (np.asarray(batch.server_loads)
                 == np.asarray(seq.server_loads)).all()
            and (np.asarray(batch.phase_time)
                 == np.asarray(seq.phase_time)).all())
    print(f"\n== trial-grid kernel sweep throughput ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials, policy={policy}, "
          f"median of {reps}) == {_device_tag()}")
    print(f"  one pallas_call for the whole sweep: {dt:8.3f}s  "
          f"{out['kernel_batch_req_s']:10.0f} req/s aggregate")
    if measure_engine:
        print(f"  vmapped jax engine, same sweep:    "
              f"{out['engine_batch_s']:8.3f}s  "
              f"{out['engine_batch_req_s']:10.0f} req/s aggregate")
    if check_bit_exact:
        print(f"  per-trial decisions/latencies/loads bit-exact vs "
              f"sequential kernel path: {out['batch_bit_exact']}"
              + ("" if out["batch_bit_exact"] else "  <-- DIVERGED"))
    return out


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def kernel_per_client_throughput(n_servers: int = 100,
                                 n_requests: int = 2000,
                                 window_size: int = 100,
                                 n_trials: int = 100, n_clients: int = 16,
                                 reps: int = 3, policy: str = "ect",
                                 threshold: float = 0.05,
                                 client_tile: Optional[int] = None,
                                 check_bit_exact: bool = False
                                 ) -> Dict[str, float]:
    """per_client contention-sweep throughput (DESIGN.md §11): the whole
    ``n_trials x n_clients`` private-log sweep as ONE 2-D
    (trials × clients) grid pallas_call —
    ``run_trials(backend='kernel', client_model='per_client')`` — vs the
    SAME sweep through the vmapped jax engine path.

    ``per_client_kernel_req_s`` / ``per_client_jax_req_s`` are aggregate
    (trials x requests) / median wall seconds; ``per_client_bit_exact``
    asserts every TrialResult decision/latency/load/aggregate of the 2-D
    grid equals the jax path — the §11 tentpole contract (also covered
    per policy/scenario in tests, so the full-scale sweeps skip it)."""
    import jax
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig

    out: Dict[str, float] = {
        "n_servers": n_servers, "n_requests": n_requests,
        "n_trials": n_trials, "n_clients": n_clients, "reps": reps,
        "policy": policy}
    key = jax.random.key(0)
    rng = "lcg" if policy in ("trh", "nltr", "two_choice") else "jax"
    pol = PolicyConfig(name=policy, threshold=threshold, rng=rng)
    warm_res = {}
    for backend in ("kernel", "jax"):
        cfg = SimConfig(n_servers=n_servers, n_requests=n_requests,
                        n_trials=n_trials, window_size=window_size,
                        n_clients=n_clients, client_model="per_client",
                        client_tile=client_tile, backend=backend,
                        scenario=ScenarioConfig(name="transient"))
        log_cfg = simulate.default_log_cfg(cfg)
        dt, warm = _median_time(
            lambda: simulate.run_trials(key, cfg, pol, log_cfg), reps)
        warm_res[backend] = warm
        tag = "kernel" if backend == "kernel" else "jax"
        out[f"per_client_{tag}_s"] = dt
        out[f"per_client_{tag}_req_s"] = n_trials * n_requests / dt
    if check_bit_exact:
        out["per_client_bit_exact"] = bool(all(
            (np.asarray(getattr(warm_res["kernel"], f))
             == np.asarray(getattr(warm_res["jax"], f))).all()
            for f in ("chosen", "latencies", "server_loads",
                      "window_loads", "phase_time", "probe_msgs")))
    print(f"\n== per_client 2-D grid sweep throughput ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials x {n_clients} clients, "
          f"policy={policy}, median of {reps}) == {_device_tag()}")
    for tag in ("kernel", "jax"):
        print(f"  {tag:>6s}: {out[f'per_client_{tag}_s']:8.3f}s  "
              f"{out[f'per_client_{tag}_req_s']:10.0f} req/s aggregate")
    if check_bit_exact:
        print(f"  TrialResult bit-exact across backends: "
              f"{out['per_client_bit_exact']}"
              + ("" if out["per_client_bit_exact"] else "  <-- DIVERGED"))
    return out


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def tuned_kernel_throughput(n_servers: int = 100, n_requests: int = 2000,
                            window_size: int = 100, n_trials: int = 100,
                            reps: int = 3, policy: str = "ect",
                            threshold: float = 0.05,
                            n_clients: Optional[int] = None
                            ) -> Dict[str, float]:
    """Tuned-lowering sweep throughput (DESIGN.md §16): the SAME kernel
    trial sweep with ``SimConfig.tiles="tuned"`` (tile shapes from the
    TUNE_sched.json table, fused-resolver fallback on a cache miss) vs
    the static default lowering, plus the bitwise equality of the two
    TrialResults — tiles are association parameters, so a tuned run must
    be the same result, just lowered faster.

    ``n_clients`` switches to the per_client 2-D grid form (the
    fused-block case: a 4-client stream wastes 28 of 32 sublanes at the
    static default client tile)."""
    import dataclasses

    import jax
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig

    cfg = SimConfig(n_servers=n_servers, n_requests=n_requests,
                    n_trials=n_trials, window_size=window_size,
                    backend="kernel",
                    n_clients=(n_clients or 1),
                    client_model=("per_client" if n_clients else
                                  "shared_log"),
                    scenario=ScenarioConfig(name="transient"))
    log_cfg = simulate.default_log_cfg(cfg)
    rng = "lcg" if policy in ("trh", "nltr", "two_choice") else "jax"
    pol = PolicyConfig(name=policy, threshold=threshold, rng=rng)
    key = jax.random.key(0)
    out: Dict[str, float] = {
        "n_servers": n_servers, "n_requests": n_requests,
        "n_trials": n_trials, "reps": reps, "policy": policy}
    # interleaved best-of-reps: the two lowerings run the same
    # deterministic work, so alternating reps and keeping each mode's
    # minimum decorrelates machine drift over the long bench run —
    # a median of back-to-back blocks recorded phantom 0.97x/1.25x
    # "speedups" that a quiet-process A/B could not reproduce
    modes = {m: dataclasses.replace(cfg, tiles=m)
             for m in ("default", "tuned")}
    warm, best = {}, {m: float("inf") for m in modes}
    for m, mcfg in modes.items():
        warm[m] = jax.block_until_ready(
            simulate.run_trials(key, mcfg, pol, log_cfg))
    for _ in range(max(reps, 1)):
        for m, mcfg in modes.items():
            t0 = time.time()
            jax.block_until_ready(simulate.run_trials(key, mcfg, pol,
                                                      log_cfg))
            best[m] = min(best[m], time.time() - t0)
    for m in modes:
        out[f"{m}_s"] = best[m]
        out[f"{m}_req_s"] = n_trials * n_requests / best[m]
    out["speedup"] = out["default_s"] / out["tuned_s"]
    out["tuned_bit_exact"] = bool(all(
        (np.asarray(getattr(warm["tuned"], f))
         == np.asarray(getattr(warm["default"], f))).all()
        for f in warm["tuned"]._fields))
    form = (f"per_client {n_clients}c 2-D grid" if n_clients
            else "trial grid")
    print(f"\n== tuned-lowering sweep throughput ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials, {form}, "
          f"policy={policy}, interleaved best of {reps}) == "
          f"{_device_tag()}")
    for mode in ("default", "tuned"):
        print(f"  {mode:>8s} tiles: {out[f'{mode}_s']:8.3f}s  "
              f"{out[f'{mode}_req_s']:10.0f} req/s aggregate")
    print(f"  tuned speedup {out['speedup']:.2f}x; TrialResult bit-exact "
          f"vs default lowering: {out['tuned_bit_exact']}"
          + ("" if out["tuned_bit_exact"] else "  <-- DIVERGED"))
    return out


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def kernel_phase_profile_point(n_servers: int = 100,
                               n_requests: int = 2000,
                               window_size: int = 100,
                               n_trials: int = 100,
                               reps: int = 3) -> Dict[str, float]:
    """Per-window-phase attribution of the full-scale trial-grid kernel
    wall time (differential over the kernel's cumulative ``ablate``
    levels, `repro.tune.profile.kernel_phase_profile`) — names WHICH
    phase owns the kernel-vs-engine gap instead of leaving it a single
    opaque number."""
    from repro.tune import profile as tune_profile

    prof = tune_profile.kernel_phase_profile(
        n_servers=n_servers, n_requests=n_requests,
        window_size=window_size, n_trials=n_trials, reps=reps)
    phases = {k: prof[k] for k in ("metrics_s", "steps_s", "plan_s",
                                   "dispatch_s")}
    gap = max(phases, key=lambda k: phases[k])
    print(f"\n== kernel per-phase profile ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials, differential over "
          f"ablate levels, median of {reps}) == {_device_tag()}")
    for k in ("total_s",) + tuple(phases):
        frac = prof[k] / max(prof["total_s"], 1e-12)
        print(f"  {k:>11s}: {prof[k]:8.3f}s  ({100 * frac:5.1f}%)")
    print(f"  dominant phase: {gap.replace('_s', '')}")
    return {**prof, "gap_phase": gap.replace("_s", "")}


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def per_client_phase_breakdown(n_servers: int = 100,
                               n_requests: int = 2000,
                               window_size: int = 100,
                               n_trials: int = 100, n_clients: int = 64,
                               reps: int = 3, policy: str = "ect",
                               threshold: float = 0.05
                               ) -> Dict[str, float]:
    """End-to-end ``run_trials`` throughput + the prep/sched/post phase
    breakdown of the batched trial pipeline (DESIGN.md §14), per backend,
    on the per_client contention instance — default the 64-client
    SHORT-STREAM case (2000 requests over 64 clients = 32-request
    slices), where the lax.map prep/post halo used to dominate the wall
    clock.

    * ``e2e_req_s_{kernel,jax}`` — jitted ``run_trials`` end-to-end
      (workload sampling through TrialResult stack), aggregate
      trials×requests / median wall seconds, batched pipeline
      (``SimConfig.prep="batched"``, the default);
    * ``e2e_seq_req_s_{kernel,jax}`` — the same dispatch with
      ``prep="sequential"`` (the lax.map halo, the pre-§14 shape);
    * ``e2e_speedup_{kernel,jax}`` — sequential wall / batched wall;
    * ``prep_s`` / ``sched_s_{kernel,jax}`` / ``post_s`` (+ the
      ``*_seq`` twins for prep/post) — each pipeline stage jitted and
      timed alone (``cfg``/``policy``/``log_cfg`` are jit statics);
    * ``e2e_batched_bit_exact`` — every TrialResult field of the
      batched pipeline equals the sequential oracle, both backends.
    """
    import dataclasses

    import jax
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig

    out: Dict[str, float] = {
        "n_servers": n_servers, "n_requests": n_requests,
        "n_trials": n_trials, "n_clients": n_clients, "reps": reps,
        "policy": policy}
    key = jax.random.key(0)
    rng = "lcg" if policy in ("trh", "nltr", "two_choice") else "jax"
    pol = PolicyConfig(name=policy, threshold=threshold, rng=rng)
    prep_jit = jax.jit(simulate._prep_trials, static_argnums=(1, 2))
    sched_jit = jax.jit(simulate._sched_trials, static_argnums=(0, 1, 2))
    post_jit = jax.jit(simulate._post_trials, static_argnums=(0,))
    bit_exact = True
    print(f"\n== per_client batched-pipeline breakdown ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials x {n_clients} clients, "
          f"policy={policy}, median of {reps}) == {_device_tag()}")
    for backend in ("kernel", "jax"):
        cfg = SimConfig(n_servers=n_servers, n_requests=n_requests,
                        n_trials=n_trials, window_size=window_size,
                        n_clients=n_clients, client_model="per_client",
                        backend=backend,
                        scenario=ScenarioConfig(name="transient"))
        log_cfg = simulate.default_log_cfg(cfg)
        cfg_seq = dataclasses.replace(cfg, prep="sequential")
        dt_b, warm_b = _median_time(
            lambda: simulate.run_trials(key, cfg, pol, log_cfg), reps)
        dt_s, warm_s = _median_time(
            lambda: simulate.run_trials(key, cfg_seq, pol, log_cfg), reps)
        bit_exact &= bool(all(
            (np.asarray(getattr(warm_b, f))
             == np.asarray(getattr(warm_s, f))).all()
            for f in warm_b._fields))
        out[f"e2e_req_s_{backend}"] = n_trials * n_requests / dt_b
        out[f"e2e_seq_req_s_{backend}"] = n_trials * n_requests / dt_s
        out[f"e2e_speedup_{backend}"] = dt_s / dt_b
        # stage breakdown: each stage jitted alone (prep/post batched
        # AND sequential; scheduling is prep-agnostic so once per
        # backend)
        keys = jax.random.split(key, n_trials)
        p_t, prep = _median_time(lambda: prep_jit(keys, cfg, log_cfg),
                                 reps)
        init, strag, works, states, traces, k_sched = prep
        s_t, sched = _median_time(
            lambda: sched_jit(cfg, pol, log_cfg, works, states, k_sched,
                              traces), reps)
        o_t, _ = _median_time(
            lambda: post_jit(cfg, init, strag, works, traces, *sched),
            reps)
        out[f"sched_s_{backend}"] = s_t
        if backend == "kernel":    # prep/post are backend-independent
            out["prep_s"], out["post_s"] = p_t, o_t
            ps_t, _ = _median_time(
                lambda: prep_jit(keys, cfg_seq, log_cfg), reps)
            os_t, _ = _median_time(
                lambda: post_jit(cfg_seq, init, strag, works, traces,
                                 *sched), reps)
            out["prep_seq_s"], out["post_seq_s"] = ps_t, os_t
        print(f"  {backend:>6s}: e2e {out[f'e2e_req_s_{backend}']:10.0f} "
              f"req/s batched vs "
              f"{out[f'e2e_seq_req_s_{backend}']:10.0f} sequential "
              f"({out[f'e2e_speedup_{backend}']:.2f}x) | stages "
              f"prep {p_t:.3f}s sched {s_t:.3f}s post {o_t:.3f}s")
    out["e2e_batched_bit_exact"] = bit_exact
    print(f"  prep {out['prep_s']:.3f}s vs sequential "
          f"{out['prep_seq_s']:.3f}s; post {out['post_s']:.3f}s vs "
          f"{out['post_seq_s']:.3f}s; TrialResult bit-exact: {bit_exact}"
          + ("" if bit_exact else "  <-- DIVERGED"))
    return out


def _forced_host_devices() -> bool:
    """True when JAX is held to the CPU (``JAX_PLATFORMS=cpu``), where
    each sharded device count needs its own process with forced host
    devices.  Decided from the environment, so the parent can start
    those processes before it touches JAX itself."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _sharded_devices_requested() -> tuple:
    """Device counts of the sharded series: ``SCHED_SHARDED_DEVICES``
    (comma list, default "1,2,4,8"; empty skips the series)."""
    env = os.environ.get("SCHED_SHARDED_DEVICES", "1,2,4,8")
    return tuple(int(t) for t in env.split(",") if t.strip())


def _sharded_env(n_devices: int) -> Dict[str, str]:
    """Env for a CPU sharded-worker subprocess: force ``n_devices`` host
    devices (replacing any count already in XLA_FLAGS) and make sure
    ``src`` is importable."""
    env = dict(os.environ)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in t]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    src = os.path.join(_REPO_ROOT, "src")
    pp = env.get("PYTHONPATH", "")
    if src not in pp.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
    return env


def _sharded_row(spec: dict, d: int) -> Dict[str, object]:
    """The 100-OSS transient Monte-Carlo sweep at ``mesh_shape=(d,)``
    over the first ``d`` devices of this process (plain single-device
    dispatch when ``d == 1``) on BOTH backends: req/s plus a sha1 digest
    of the per-trial decisions/latencies/phase times — compared across
    device counts for the DESIGN.md §12 bit-exactness claim."""
    import jax
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig

    if jax.device_count() % d:
        raise ValueError(f"{d} devices do not divide jax.device_count()="
                         f"{jax.device_count()}")
    key = jax.random.key(0)
    pol = PolicyConfig(name="ect", threshold=0.05)
    out: Dict[str, object] = {"devices": d, **device_info()}
    for backend in ("kernel", "jax"):
        cfg = SimConfig(n_servers=spec["n_servers"],
                        n_requests=spec["n_requests"],
                        n_trials=spec["n_trials"],
                        window_size=spec["window_size"], backend=backend,
                        mesh_shape=None if d == 1 else (d,),
                        scenario=ScenarioConfig(name="transient"))
        log_cfg = simulate.default_log_cfg(cfg)
        dt, warm = _median_time(
            lambda: simulate.run_trials(key, cfg, pol, log_cfg),
            spec["reps"])
        h = hashlib.sha1()
        for f in ("chosen", "latencies", "phase_time"):
            h.update(np.asarray(getattr(warm, f)).tobytes())
        out[f"{backend}_s"] = dt
        out[f"{backend}_req_s"] = spec["n_trials"] * spec["n_requests"] / dt
        out[f"{backend}_digest"] = h.hexdigest()
    return out


def _sharded_worker(spec: dict) -> None:
    """Body of one CPU ``--sharded-worker`` subprocess: its forced host
    device count must be exactly ``spec["devices"]``; prints one
    ``SHARDED_RESULT`` json line."""
    import jax
    d = int(spec["devices"])
    if jax.device_count() != d:
        raise RuntimeError(f"sharded worker expected {d} forced host "
                           f"devices, JAX sees {jax.device_count()}")
    print("SHARDED_RESULT " + json.dumps(_sharded_row(spec, d)), flush=True)


@functools.lru_cache(maxsize=None)   # run_all + emit_bench_point share it
def sharded_sweep_throughput(n_servers: int = 100, n_requests: int = 2000,
                             window_size: int = 100, n_trials: int = 100,
                             reps: int = 1,
                             devices: tuple = (1, 2, 4, 8)
                             ) -> Dict[str, object]:
    """Sharded sweep throughput (DESIGN.md §12): the full Monte-Carlo
    sweep through ``parallel/sweep.py`` at each device count in
    ``devices``, both backends, vs the single-device dispatch (the
    ``devices == 1`` row).  The digests of (chosen, latencies,
    phase_time) must agree across every device count AND across
    backends — the sharded dispatch is a pure re-layout.

    On a chip (or any backend but the forced-CPU one) every count runs
    in THIS process over a sub-mesh of its first ``d`` devices, and only
    the counts that divide ``jax.device_count()`` are measured: a chip
    belongs to one process, so no child could reach it.  Under
    ``JAX_PLATFORMS=cpu`` each count runs in its own SUBPROCESS with
    ``--xla_force_host_platform_device_count=N`` (XLA fixes the host
    device count at first jax init); call this before the parent
    touches JAX.  Forced host "devices" are threads on the same CPU, so
    those rows measure dispatch overhead, not scaling."""
    spec = {"n_servers": n_servers, "n_requests": n_requests,
            "window_size": window_size, "n_trials": n_trials, "reps": reps}
    out: Dict[str, object] = dict(spec)
    rows = {}
    if _forced_host_devices():
        for d in devices:
            r = subprocess.run(
                [sys.executable, "-m", "benchmarks.sched_perf",
                 "--sharded-worker", json.dumps({**spec, "devices": d})],
                cwd=_REPO_ROOT, env=_sharded_env(d),
                capture_output=True, text=True)
            line = next((ln for ln in r.stdout.splitlines()
                         if ln.startswith("SHARDED_RESULT ")), None)
            if r.returncode != 0 or line is None:
                raise RuntimeError(
                    f"sharded worker (devices={d}) failed:\n"
                    f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            rows[d] = json.loads(line[len("SHARDED_RESULT "):])
    else:
        import jax
        n = jax.device_count()
        devices = tuple(d for d in devices if n % d == 0)
        for d in devices:
            rows[d] = _sharded_row(spec, d)
    out["devices"] = list(devices)
    if not devices:
        return out
    base = rows[devices[0]]
    out["platform"] = base["platform"]
    out["device_kind"] = base["device_kind"]
    out["sharded_bit_exact"] = all(
        rows[d][f"{b}_digest"] == base[f"{b}_digest"]
        for d in devices for b in ("kernel", "jax"))
    out["sharded_cross_backend_exact"] = all(
        rows[d]["kernel_digest"] == rows[d]["jax_digest"] for d in devices)
    for d in devices:
        out[f"sharded_req_s_{d}d"] = rows[d]["kernel_req_s"]
        out[f"sharded_engine_req_s_{d}d"] = rows[d]["jax_req_s"]
    print(f"\n== sharded sweep throughput ({n_servers} OSS x "
          f"{n_requests} reqs x {n_trials} trials, mesh=(d,), "
          f"median of {reps}) == [{base['platform']} "
          f"{base['device_kind']}, d devices of the row]")
    print(f"{'devices':>8s} {'kernel req/s':>14s} {'engine req/s':>14s}")
    for d in devices:
        print(f"{d:>8d} {out[f'sharded_req_s_{d}d']:14.0f} "
              f"{out[f'sharded_engine_req_s_{d}d']:14.0f}")
    print(f"  bit-exact across device counts: {out['sharded_bit_exact']}"
          + ("" if out["sharded_bit_exact"] else "  <-- DIVERGED"))
    print(f"  bit-exact across backends:      "
          f"{out['sharded_cross_backend_exact']}"
          + ("" if out["sharded_cross_backend_exact"] else "  <-- DIVERGED"))
    return out


def scenario_ranking(n_trials: int = 25) -> Dict[str, Dict[str, dict]]:
    """Policy ranking per scenario: p50/p95/p99 latency + makespan +
    straggler-hit fraction (jitted run_trials sweep)."""
    from repro.core import analysis
    out = _scenario_sweep(SWEEP_SCENARIOS, SWEEP_POLICIES, n_trials)
    table: Dict[str, Dict[str, dict]] = {}
    print("\n== Temporal scenarios: policy ranking "
          f"(est. completion latency, s) == {_device_tag()}")
    for scn, row in out.items():
        print(f"\n-- scenario: {scn} --")
        print(f"{'policy':>8s} {'p50':>8s} {'p95':>8s} {'p99':>8s} "
              f"{'makespan':>9s} {'strag_hit%':>10s}")
        ranked = {}
        for pol, res in row.items():
            ls = analysis.latency_stats(res.latencies)
            ls["makespan"] = analysis.makespan(res)
            ls["hit_frac"] = analysis.straggler_summary(res)["hit_fraction"]
            ranked[pol] = ls
            print(f"{pol:>8s} {ls['p50']:8.2f} {ls['p95']:8.2f} "
                  f"{ls['p99']:8.2f} {ls['makespan']:9.2f} "
                  f"{100 * ls['hit_frac']:10.2f}")
        best = min(ranked, key=lambda p: ranked[p]["p99"])
        print(f"   best p99: {best} "
              f"({ranked[best]['p99'] / max(ranked['rr']['p99'], 1e-9):.2f}x rr)")
        table[scn] = ranked
    return table


def transient_latency_cdf(n_trials: int = 25) -> None:
    """Latency CDF under the transient straggler trace (rr vs trh vs ect)."""
    from repro.core import analysis
    out = _transient_results(n_trials)
    print(f"\n== Transient stragglers: request latency CDF == "
          f"{_device_tag()}")
    for pol, res in out.items():
        xs, ys = analysis.latency_cdf(res.latencies, 72)
        print(analysis.ascii_plot(
            ys, label=f"CDF P[lat<=x] — {pol} "
                      f"(x: 0..{xs[-1]:.1f}s, p99={analysis.latency_stats(res.latencies)['p99']:.2f}s)"))


def emit_bench_point(path: str = BENCH_PATH,
                     n_trials: int = 25,
                     kernel_scale: int = 100,
                     batch_trials: int = 100) -> dict:
    """Append one perf-trajectory point: the §Perf C phase time per policy,
    the transient-scenario p99 for the log-assisted policies, the
    kernel-backend numbers (wall time of scheduling the 100-OSS transient
    stream through the Pallas backend + req/s for both backends), the
    trial-grid sweep throughput (`kernel_batch_req_s`: the full
    100 OSS x 2000 req x ``batch_trials`` sweep as ONE pallas_call), and
    the sharded-sweep series (`sharded_req_s_{d}d`, DESIGN.md §12) at
    the device counts in ``SCHED_SHARDED_DEVICES`` (comma list, default
    "1,2,4,8"; set empty to skip the sharded sweeps; on a chip only the
    counts dividing the device count run).  The point names the device
    (``platform``, ``device_kind``, ``device_count``).
    All throughput cells are medians of ``reps`` repeats (recorded in
    the point).  Points are keyed by ``git_sha``: re-running on the same
    commit REPLACES that commit's point instead of appending a
    duplicate, and each point stamps ``git_dirty`` so points measured
    on an uncommitted tree are distinguishable from their commit's.
    The sort-policy rows carry same-policy engine twins
    (``engine_req_s_{mlml,nltr}``) for the behind-engine flag.
    Reuses this process's cached run_all results."""
    from repro.core import analysis
    point: Dict[str, object] = {"ts": time.time(), "metric_unit": "seconds",
                                **device_info()}
    # call signatures mirror run_all's rows so the lru_cache hits
    for pol, kw in (("rr", {}), ("trh", {"threshold": 4.0}),
                    ("ect", {"threshold": 0.05})):
        point[f"phase_s_{pol}"] = phase_time(policy=pol, **kw)["phase_s"]
    for pol, res in _transient_results(n_trials).items():
        point[f"transient_p99_{pol}"] = \
            analysis.latency_stats(res.latencies)["p99"]
    thr = kernel_vs_engine_throughput(n_servers=kernel_scale)
    point["kernel_backend_phase_s"] = thr["kernel_s"]
    point["kernel_req_s"] = thr["kernel_req_s"]
    point["engine_req_s"] = thr["jax_req_s"]
    point["kernel_bit_exact"] = thr["bit_exact"]
    point["bench_reps"] = thr["reps"]
    bat = kernel_batch_throughput(n_servers=kernel_scale,
                                  n_trials=batch_trials)
    point["kernel_batch_req_s"] = bat["kernel_batch_req_s"]
    point["kernel_batch_trials"] = bat["n_trials"]
    point["kernel_batch_bit_exact"] = bat.get("batch_bit_exact")
    # sort-based policies through the same trial-grid kernel (§13 fast
    # path), with their SAME-POLICY engine twins so the trajectory's
    # behind-engine flag can fire for them; parity is covered by tests,
    # so skip the lax.map re-check here
    for spol in ("mlml", "nltr"):
        sb = kernel_batch_throughput(n_servers=kernel_scale,
                                     n_trials=batch_trials, policy=spol,
                                     threshold=5.0, check_bit_exact=False,
                                     measure_engine=True)
        point[f"kernel_batch_req_s_{spol}"] = sb["kernel_batch_req_s"]
        point[f"engine_req_s_{spol}"] = sb["engine_batch_req_s"]
    # per_client contention sweeps on the 2-D (trials × clients) grid
    # (DESIGN.md §11): kernel vs the vmapped jax path at {4, 16, 64}
    # clients; 16 is the headline pair tracked by --trajectory and
    # carries the full-scale bit-exactness flag
    for n_c in (4, 16, 64):
        pc = kernel_per_client_throughput(n_servers=kernel_scale,
                                          n_trials=batch_trials,
                                          n_clients=n_c,
                                          check_bit_exact=(n_c == 16))
        suffix = "" if n_c == 16 else f"_{n_c}c"
        point[f"kernel_batch_req_s_per_client{suffix}"] = \
            pc["per_client_kernel_req_s"]
        point[f"engine_req_s_per_client{suffix}"] = \
            pc["per_client_jax_req_s"]
        if n_c == 16:
            point["kernel_per_client_bit_exact"] = \
                pc.get("per_client_bit_exact")
    # batched trial pipeline (DESIGN.md §14): end-to-end run_trials and
    # the prep/sched/post phase breakdown at the 64-client short-stream
    # instance — the case where the lax.map prep/post halo dominated
    pb = per_client_phase_breakdown(n_servers=kernel_scale,
                                    n_trials=batch_trials, n_clients=64)
    for k in ("e2e_req_s_kernel", "e2e_req_s_jax",
              "e2e_seq_req_s_kernel", "e2e_seq_req_s_jax",
              "prep_s", "sched_s_kernel", "sched_s_jax", "post_s",
              "prep_seq_s", "post_seq_s"):
        point[k] = pb[k]
    point["e2e_batched_bit_exact"] = pb["e2e_batched_bit_exact"]
    # sharded sweep series (DESIGN.md §12): the same full-scale sweep
    # through parallel/sweep.py at each device count (see
    # sharded_sweep_throughput); env-gated because each count pays its
    # own compile + warmup
    devs = _sharded_devices_requested()
    if devs:
        sh = sharded_sweep_throughput(n_servers=kernel_scale,
                                      n_trials=batch_trials, devices=devs)
        for d in sh["devices"]:
            point[f"sharded_req_s_{d}d"] = sh[f"sharded_req_s_{d}d"]
            point[f"sharded_engine_req_s_{d}d"] = \
                sh[f"sharded_engine_req_s_{d}d"]
        if sh["devices"]:
            point["sharded_bit_exact"] = bool(
                sh["sharded_bit_exact"]
                and sh["sharded_cross_backend_exact"])
    # §16 tuned-lowering series: the same full-scale sweeps with tiles
    # resolved through the tuner table (fused-resolver fallback on a
    # cache miss), each with its bit-exact flag vs the default lowering
    # and the tuned/default speedup; the per_client 4-client row is the
    # fused multi-trial block case (28 of 32 sublanes idle at the static
    # default client tile)
    for spol, thr_ in (("ect", 0.05), ("mlml", 5.0), ("nltr", 5.0)):
        tn = tuned_kernel_throughput(n_servers=kernel_scale,
                                     n_trials=batch_trials, policy=spol,
                                     threshold=thr_)
        suffix = "" if spol == "ect" else f"_{spol}"
        point[f"tuned_kernel_req_s{suffix}"] = tn["tuned_req_s"]
        point[f"tuned_speedup{suffix}"] = tn["speedup"]
        point[f"tuned_bit_exact{suffix}"] = tn["tuned_bit_exact"]
    tp = tuned_kernel_throughput(n_servers=kernel_scale,
                                 n_trials=batch_trials, n_clients=4)
    point["tuned_kernel_req_s_per_client_4c"] = tp["tuned_req_s"]
    point["tuned_speedup_per_client_4c"] = tp["speedup"]
    point["tuned_bit_exact_per_client_4c"] = tp["tuned_bit_exact"]
    # §16 per-phase kernel profile: attributes the kernel-vs-engine gap
    # to a NAMED window phase (differential over ablate levels)
    prof = kernel_phase_profile_point(n_servers=kernel_scale,
                                      n_trials=batch_trials)
    for k in ("total_s", "metrics_s", "steps_s", "plan_s", "dispatch_s"):
        point[f"kernel_phase_{k}"] = prof[k]
    point["kernel_gap_phase"] = prof["gap_phase"]
    # contract linter (DESIGN.md §15): lint wall time as a trajectory
    # series plus the clean flag — a point measured on a dirty-contract
    # tree is visibly tainted
    from repro.contractcheck import check_tree, load_config
    t_lint = time.time()
    lint_live = [f for f in check_tree(load_config())
                 if not f.suppressed]
    point["contractcheck_s"] = time.time() - t_lint
    point["contractcheck_clean"] = not lint_live
    _stamp_git(point)
    sha = point.get("git_sha")
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = [history]
        except (json.JSONDecodeError, OSError):
            history = []
    # one point per commit: a re-run on the same HEAD replaces its
    # earlier point (uncommitted tweaks would otherwise pile up
    # same-sha near-duplicates and skew the delta table)
    if sha:
        history = [p for p in history
                   if not (isinstance(p, dict) and p.get("git_sha") == sha)]
    history.append(point)
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    print(f"[sched_perf] appended point -> {path} "
          f"(trh phase {point['phase_s_trh']:.2f}s, "
          f"transient p99 {point['transient_p99_trh']:.2f}s)")
    return point


def trajectory(path: str = BENCH_PATH,
               fig_path: str = os.path.join(
                   _REPO_ROOT, "BENCH_sched_trajectory.png")) -> list:
    """Perf trajectory across benchmark runs: stdout table of phase-time
    deltas plus a plotted figure (matplotlib when available, ascii-plot
    file otherwise).  Each `benchmarks/run.py` invocation appends one
    point; this renders the history."""
    from repro.core import analysis
    if not os.path.exists(path):
        print(f"[trajectory] {path} not found — run benchmarks first")
        return []
    # Tolerant history load: a zero-byte / half-written / corrupt file
    # (e.g. an interrupted emit_bench_point) must render as "empty", not
    # crash the whole benchmark report.
    try:
        with open(path) as f:
            history = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        print(f"[trajectory] {path} is empty or unreadable ({e}) — "
              "run benchmarks first")
        return []
    if not isinstance(history, list):
        history = [history]
    # points must be dicts; anything else (schema drift) is dropped, not
    # crashed on — older points simply miss the newer series
    history = [pt for pt in history if isinstance(pt, dict)]
    if not history:
        print(f"[trajectory] {path} is empty")
        return history

    cols = ("phase_s_rr", "phase_s_trh", "phase_s_ect",
            "transient_p99_trh", "kernel_backend_phase_s")
    # scheduling throughput series (req/s — higher is better); the
    # delta table flags any run where a kernel path fell behind the
    # engine (the regression the trial-grid kernel exists to prevent).
    # Older points predate the later series (kernel_batch_req_s, the
    # sort-policy rows, the per_client 2-D-grid pair) — every access is
    # a tolerant .get.
    thr_cols = ("engine_req_s", "kernel_req_s", "kernel_batch_req_s",
                "tuned_kernel_req_s",
                "kernel_batch_req_s_mlml", "engine_req_s_mlml",
                "tuned_kernel_req_s_mlml",
                "kernel_batch_req_s_nltr", "engine_req_s_nltr",
                "tuned_kernel_req_s_nltr",
                "kernel_batch_req_s_per_client", "engine_req_s_per_client",
                "tuned_kernel_req_s_per_client_4c",
                "e2e_req_s_kernel", "e2e_seq_req_s_kernel",
                "e2e_req_s_jax", "e2e_seq_req_s_jax",
                "sharded_req_s_8d", "sharded_engine_req_s_8d")
    print(f"\n== perf trajectory ({len(history)} runs, {path}) ==")
    # the dirty column marks points measured on an uncommitted tree
    # (git_dirty=true): their numbers are real but must never serve as
    # regression baselines — "·" is clean, "D" is dirty, "?" predates
    # the stamp
    print(f"{'run':>4s} {'when':>16s} {'dirty':>5s} " +
          " ".join(f"{c.replace('phase_s_', 'ph_'):>14s}" for c in cols))
    prev = None
    for i, pt in enumerate(history):
        when = time.strftime("%m-%d %H:%M", time.localtime(pt.get("ts", 0)))
        dirty = ("?" if "git_dirty" not in pt
                 else "D" if pt["git_dirty"] else "·")
        cells = []
        for c in cols:
            v = pt.get(c)
            if v is None:
                cells.append(f"{'—':>14s}")
            elif prev is not None and isinstance(prev.get(c), (int, float)):
                d = v - prev[c]
                cells.append(f"{v:8.2f}{d:+6.2f}")
            else:
                cells.append(f"{v:8.2f}{'':>6s}")
        print(f"{i:>4d} {when:>16s} {dirty:>5s} " + " ".join(cells))
        prev = pt

    # only the SAME-policy kernel series compare against engine_req_s;
    # the sort-policy rows compare against THEIR engine twins
    # (engine_req_s_{mlml,nltr}, emitted since the §13 fast path) and
    # the per_client kernel series against ITS jax twin — flagging any
    # of them against the ect engine number would be apples-to-oranges.
    flag_cols = ("kernel_req_s", "kernel_batch_req_s")
    print(f"\n{'run':>4s} " + " ".join(f"{c:>20s}" for c in thr_cols)
          + "  kernel vs engine")
    for i, pt in enumerate(history):
        eng = pt.get("engine_req_s")
        cells = []
        behind = []
        for c in thr_cols:
            v = pt.get(c)
            cells.append(f"{'—':>20s}" if v is None else f"{v:20.0f}")
            if (v is not None and eng is not None and c in flag_cols
                    and v < eng):
                behind.append(c.replace("_req_s", ""))
        pck = pt.get("kernel_batch_req_s_per_client")
        pce = pt.get("engine_req_s_per_client")
        if pck is not None and pce is not None and pck < pce:
            behind.append("kernel_batch_per_client")
        for spol in ("mlml", "nltr"):
            sk = pt.get(f"kernel_batch_req_s_{spol}")
            se = pt.get(f"engine_req_s_{spol}")
            if sk is not None and se is not None and sk < se:
                behind.append(f"kernel_batch_{spol}")
        # tuned series compare against their UNTUNED kernel twins — the
        # tuner's whole contract is "never slower than the static
        # default lowering"
        for tuned, untuned in (
                ("tuned_kernel_req_s", "kernel_batch_req_s"),
                ("tuned_kernel_req_s_mlml", "kernel_batch_req_s_mlml"),
                ("tuned_kernel_req_s_nltr", "kernel_batch_req_s_nltr"),
                ("tuned_kernel_req_s_per_client_4c",
                 "kernel_batch_req_s_per_client_4c")):
            tk, uk = pt.get(tuned), pt.get(untuned)
            if tk is not None and uk is not None and tk < uk:
                behind.append(tuned.replace("_kernel_req_s", ""))
        # sharded series compare ONLY against the same-device-count
        # engine twin — a 2-device sharded row vs the 1-device engine
        # number would conflate scaling with backend speed
        for d_ct in (2, 4, 8):
            sk = pt.get(f"sharded_req_s_{d_ct}d")
            se = pt.get(f"sharded_engine_req_s_{d_ct}d")
            if sk is not None and se is not None and sk < se:
                behind.append(f"sharded_{d_ct}d")
        # batched-pipeline series compare ONLY against their SAME-backend
        # sequential (lax.map-halo) twin — the regression the §14 batched
        # prep/post exists to prevent is "batched slower than the halo",
        # not "jax e2e slower than kernel e2e"
        for be in ("kernel", "jax"):
            eb = pt.get(f"e2e_req_s_{be}")
            es = pt.get(f"e2e_seq_req_s_{be}")
            if eb is not None and es is not None and eb < es:
                behind.append(f"e2e_batched_{be}")
        flag = ("  <-- " + ", ".join(behind) + " BEHIND baseline"
                if behind else "")
        print(f"{i:>4d} " + " ".join(cells) + flag)

    series = {c: [pt.get(c) for pt in history] for c in cols}
    thr_series = {c: [pt.get(c) for pt in history] for c in thr_cols}
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, (ax, ax2) = plt.subplots(2, 1, figsize=(8, 7), sharex=True)
        for c in cols:
            ys = series[c]
            if any(v is not None for v in ys):
                ax.plot(range(len(ys)),
                        [float("nan") if v is None else v for v in ys],
                        marker="o", label=c)
        ax.set_ylabel("seconds")
        ax.set_title("scheduler perf trajectory (BENCH_sched.json)")
        ax.legend(fontsize=8)
        for c in thr_cols:
            ys = thr_series[c]
            if any(v is not None for v in ys):
                ax2.plot(range(len(ys)),
                         [float("nan") if v is None else v for v in ys],
                         marker="s", label=c)
        ax2.set_xlabel("benchmark run")
        ax2.set_ylabel("req/s (higher is better)")
        ax2.legend(fontsize=8)
        fig.tight_layout()
        fig.savefig(fig_path, dpi=120)
        print(f"[trajectory] figure -> {fig_path}")
    except ImportError:
        txt_path = fig_path.rsplit(".", 1)[0] + ".txt"
        with open(txt_path, "w") as f:
            for c in cols + thr_cols:
                ys = [v for v in {**series, **thr_series}[c]
                      if v is not None]
                if len(ys) >= 2:
                    f.write(analysis.ascii_plot(
                        np.asarray(ys), label=f"{c} per run") + "\n")
        print(f"[trajectory] matplotlib unavailable; ascii figure -> "
              f"{txt_path}")
    return history


def run_smoke() -> None:
    """CI benchmark smoke: a fast subset proving the host path, the jitted
    sweep, the kernel backend AND the trial-grid dispatch all still run
    (sched_perf --smoke)."""
    print("== sched_perf --smoke ==")
    t0 = time.time()
    r = phase_time(policy="rr", n_files=24)
    e = phase_time(policy="ect", threshold=0.05, n_files=24)
    print(f"  phase_s rr={r['phase_s']:.2f} ect={e['phase_s']:.2f} "
          f"(24 files)")
    assert e["phase_s"] <= r["phase_s"] * 1.05, (e, r)
    thr = kernel_vs_engine_throughput(n_servers=24, n_requests=480,
                                      window_size=60, reps=1)
    assert thr["bit_exact"], "kernel/engine divergence"
    # trial-grid dispatch: T=10 is NOT a multiple of the default tile
    # (8), so the smoke also covers the inert-padded-trial path pre-merge
    bat = kernel_batch_throughput(n_servers=24, n_requests=480,
                                  window_size=60, n_trials=10, reps=1)
    assert bat["batch_bit_exact"], "trial-grid/sequential divergence"
    # a SORT-BASED policy through the batch kernel: the in-VMEM bitonic
    # request sort + section bounds path (DESIGN.md §10) must stay
    # bit-exact vs the lax.map sequential kernel pre-merge
    srt = kernel_batch_throughput(n_servers=24, n_requests=480,
                                  window_size=60, n_trials=10, reps=1,
                                  policy="nltr", threshold=4.0)
    assert srt["batch_bit_exact"], "sort-policy trial-grid divergence"
    # mlml rides the same §13 permutation-apply fast path (all-pairs
    # rank + vectorized sort/unsort applies): bit-exactness AND a
    # timing guard — the fast path keeps a sort policy within a small
    # factor of the ect batch wall time (the pre-§13 bitonic networks
    # sat ~10x behind; 8x leaves headroom for CI jitter at reps=1)
    sml = kernel_batch_throughput(n_servers=24, n_requests=480,
                                  window_size=60, n_trials=10, reps=1,
                                  policy="mlml", threshold=4.0)
    assert sml["batch_bit_exact"], "mlml trial-grid divergence"
    assert sml["batch_s"] <= 8.0 * bat["batch_s"], (
        "mlml batch fell behind the §13 fast-path envelope",
        sml["batch_s"], bat["batch_s"])
    # per_client on the 2-D (trials × clients) grid (DESIGN.md §11):
    # T=10 vs trial tile 8 AND C=5 over client_tile=2 exercise inert
    # trial padding, phantom-client padding AND the multi-block
    # cross-client accumulator; the whole TrialResult must match the
    # jax path (the default tile would clamp to 5 — one block, no pad)
    pc = kernel_per_client_throughput(n_servers=24, n_requests=480,
                                      window_size=60, n_trials=10,
                                      n_clients=5, client_tile=2, reps=1,
                                      check_bit_exact=True)
    assert pc["per_client_bit_exact"], "per_client 2-D grid divergence"
    # merged-p99 lane (DESIGN.md §14): on a small per_client grid the
    # kernel's in-VMEM MET_P99 == the host `nearest_rank_p99` bisection
    # over its merged latency block == the bisection over the jax
    # grouped-block twin rebuilt from the request-order latencies
    import jax
    import jax.numpy as jnp
    from repro.core import engine, policy_core, statlog
    t_g, c_g, m_g, ws_g, per = 2, 3, 12, 4, 8
    lcfg = statlog.LogConfig(n_servers=m_g)
    ko, kl, kk2 = jax.random.split(jax.random.key(9), 3)
    works = engine.Workload(
        jax.random.randint(ko, (t_g, c_g, per), 0, 8 * m_g,
                           dtype=jnp.int32),
        jax.random.uniform(kl, (t_g, c_g, per), minval=1.0, maxval=4.0),
        jnp.ones((t_g, c_g, per), bool).at[:, -1, per // 2:].set(False))
    states = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (t_g, c_g) + a.shape),
        statlog.init_state(lcfg))
    gkeys = jax.vmap(lambda k_: jax.random.split(k_, c_g))(
        jax.random.split(kk2, t_g))
    res_g, _, merged = engine.run_stream_batch(
        states, works, gkeys,
        policy=PolicyConfig(name="ect", threshold=0.05), log_cfg=lcfg,
        window_size=ws_g, backend="kernel")
    host = policy_core.nearest_rank_p99(
        merged.lats.reshape(t_g, -1),
        merged.lats_valid.reshape(t_g, -1) != 0.0)[:, 0]
    assert (np.asarray(merged.metrics[:, policy_core.MET_P99])
            == np.asarray(host)).all(), "kernel MET_P99 != host bisection"
    g_lat, g_val = engine.grouped_latency_block(works, res_g.latencies,
                                                ws_g)
    twin = policy_core.nearest_rank_p99(
        g_lat.reshape(t_g, -1), g_val.reshape(t_g, -1))[:, 0]
    assert (np.asarray(host) == np.asarray(twin)).all(), \
        "kernel merged latency block != jax grouped-block twin"
    print("  merged p99 (in-VMEM block vs host bisection vs jax twin) "
          "bit-exact: True")
    # batched trial pipeline (DESIGN.md §14): the vmapped prep/post
    # stack must equal the lax.map sequential oracle bit-for-bit
    import dataclasses
    from repro.core import simulate
    from repro.core.simulate import ScenarioConfig, SimConfig
    cfg_b = SimConfig(n_servers=24, n_requests=240, n_trials=5,
                      window_size=60, backend="kernel",
                      scenario=ScenarioConfig(name="transient"))
    log_b = simulate.default_log_cfg(cfg_b)
    pol_b = PolicyConfig(name="ect", threshold=0.05)
    key_b = jax.random.key(0)
    r_bat = simulate.run_trials(key_b, cfg_b, pol_b, log_b)
    r_seq = simulate.run_trials(
        key_b, dataclasses.replace(cfg_b, prep="sequential"), pol_b, log_b)
    assert all((np.asarray(getattr(r_bat, f))
                == np.asarray(getattr(r_seq, f))).all()
               for f in r_bat._fields), "batched prep != sequential oracle"
    print("  batched prep/post pipeline bit-exact vs lax.map oracle: True")
    # lowering autotuner (DESIGN.md §16): one tiny tune round into a
    # throwaway table, then a tuned-tiles kernel run must be bit-exact
    # with the DEFAULT-tile jax engine twin — trial_tile is
    # lowering-only and the tuned client tile resolves identically
    # cross-backend (the kernel-key fallback), so tuning can never move
    # a result
    import tempfile
    from repro.tune import autotune
    from repro.tune import profile as tune_profile
    old_tune_path = os.environ.get("SCHED_TUNE_PATH")
    with tempfile.TemporaryDirectory() as td:
        os.environ["SCHED_TUNE_PATH"] = os.path.join(td, "TUNE.json")
        try:
            cfg_t = SimConfig(n_servers=24, n_requests=480, n_trials=10,
                              window_size=60, backend="kernel",
                              scenario=ScenarioConfig(name="transient"))
            log_t = simulate.default_log_cfg(cfg_t)
            pol_t = PolicyConfig(name="ect", threshold=0.05, rng="lcg")
            _, entry = autotune.tune_config(cfg_t, pol_t, reps=1)
            r_tuned = simulate.run_trials(
                jax.random.key(0),
                dataclasses.replace(cfg_t, tiles="tuned"), pol_t, log_t)
            r_twin = simulate.run_trials(
                jax.random.key(0),
                dataclasses.replace(cfg_t, backend="jax"), pol_t, log_t)
            assert all((np.asarray(getattr(r_tuned, f))
                        == np.asarray(getattr(r_twin, f))).all()
                       for f in r_tuned._fields), \
                "tuned-tile kernel != default-tile engine twin"
            print(f"  tuned tiles (tt={entry['trial_tile']}) bit-exact vs "
                  "default-tile engine twin: True")
        finally:
            if old_tune_path is None:
                os.environ.pop("SCHED_TUNE_PATH", None)
            else:
                os.environ["SCHED_TUNE_PATH"] = old_tune_path
    # ablate phase profiling sanity: levels are cumulative, so every
    # differential phase is nonnegative and the full run dominates
    prof = tune_profile.kernel_phase_profile(
        n_servers=24, n_requests=480, window_size=60, n_trials=6, reps=1)
    assert prof["total_s"] > 0 and all(
        prof[k] >= 0 for k in ("metrics_s", "steps_s", "plan_s",
                               "dispatch_s")), prof
    print(f"  ablate phase profile sane (total {prof['total_s']:.3f}s)")
    # sharded sweep (DESIGN.md §12) when the process has devices to
    # shard over (CI's multidevice job forces 8): the whole mesh=(dc,)
    # sweep must be bit-exact vs this process's single-device dispatch,
    # both backends
    import jax
    dc = jax.device_count()
    if dc >= 2:
        from repro.core import simulate
        from repro.core.simulate import ScenarioConfig, SimConfig
        key = jax.random.key(0)
        pol = PolicyConfig(name="ect", threshold=0.05)
        for backend in ("kernel", "jax"):
            res = {}
            for ms in (None, (dc,)):
                cfg = SimConfig(n_servers=24, n_requests=480,
                                window_size=60, n_trials=10,
                                backend=backend, mesh_shape=ms,
                                scenario=ScenarioConfig(name="transient"))
                res[ms] = simulate.run_trials(
                    key, cfg, pol, simulate.default_log_cfg(cfg))
            same = all(
                (np.asarray(getattr(res[None], f))
                 == np.asarray(getattr(res[(dc,)], f))).all()
                for f in ("chosen", "latencies", "phase_time"))
            assert same, f"sharded {backend} sweep != single-device"
            print(f"  sharded mesh=({dc},) {backend} sweep bit-exact vs "
                  f"single-device: True")
    else:
        print("  sharded smoke skipped (1 device; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=N)")
    # contract linter (DESIGN.md §15): the AST layer over the whole
    # scoped surface must be strict-clean pre-merge (the CI `contract`
    # job adds the jaxpr layer); timed so a lint slowdown is visible
    from repro.contractcheck import check_tree, load_config
    t_lint = time.time()
    lint_live = [f for f in check_tree(load_config())
                 if not f.suppressed]
    assert not lint_live, [f.format() for f in lint_live]
    print(f"  contractcheck AST layer strict-clean "
          f"({time.time() - t_lint:.2f}s)")
    _scenario_sweep(("transient",), ("rr", "ect"), 4)
    print(f"[smoke] ok in {time.time() - t0:.1f}s")


def run_all() -> None:
    print("\n== §Perf C: scheduler hillclimb (phase completion time) ==")
    print(f"  ideal (napkin) phase time ~ {ideal_phase_time():.2f}s "
          f"(bytes / healthy aggregate, floored by srv5 queue)")
    print(f"{'iter':>28s} {'phase_s':>8s} {'strag_hits':>10s} "
          f"{'probes':>7s} {'redirects':>9s}")

    def row(tag, **kw):
        r = phase_time(**kw)
        print(f"{tag:>28s} {r['phase_s']:8.2f} "
              f"{r['straggler_hits']:10d} {r['probes']:7d} "
              f"{r['redirect_entries']:9d}")
        return r

    row("baseline rr", policy="rr")
    row("two_choice (SC'14, probes)", policy="two_choice")
    row("trh thr=64 (too shy)", policy="trh", threshold=64.0)
    row("trh thr=16", policy="trh", threshold=16.0)
    row("trh thr=4", policy="trh", threshold=4.0)
    row("trh thr=0.5 (eager)", policy="trh", threshold=0.5)
    row("mlml thr=4", policy="mlml", threshold=4.0)
    row("nltr thr=4", policy="nltr", threshold=4.0)
    row("trh stripe=16MB (coarse)", policy="trh", stripe_mb=16.0)
    row("trh stripe=1MB (fine)", policy="trh", stripe_mb=1.0)
    row("trh thr=4 + warm probs", policy="trh", threshold=4.0,
        warm_probs=True)
    row("mlml thr=4 + warm probs", policy="mlml", threshold=4.0,
        warm_probs=True)
    row("nltr thr=4 + warm probs", policy="nltr", threshold=4.0,
        warm_probs=True)
    row("trh + prob refresh/window", policy="trh", threshold=4.0,
        warm_probs=True, refresh=True)
    row("mlml + prob refresh/window", policy="mlml", threshold=4.0,
        warm_probs=True, refresh=True)
    row("nltr + prob refresh/window", policy="nltr", threshold=4.0,
        warm_probs=True, refresh=True)
    row("ect thr=0.05s (rate-aware)", policy="ect", threshold=0.05)
    row("ect + fine stripes", policy="ect", threshold=0.05, stripe_mb=1.0)
    row("ect cold log (no snapshot)", policy="ect", threshold=0.05,
        know_loads=False)

    scenario_ranking()
    transient_latency_cdf()
    # keyword calls match emit_bench_point's exactly so the lru_cache hits
    kernel_vs_engine_throughput(n_servers=100)
    kernel_batch_throughput(n_servers=100, n_trials=100)
    for spol in ("mlml", "nltr"):
        kernel_batch_throughput(n_servers=100, n_trials=100, policy=spol,
                                threshold=5.0, check_bit_exact=False,
                                measure_engine=True)
    for n_c in (4, 16, 64):
        kernel_per_client_throughput(n_servers=100, n_trials=100,
                                     n_clients=n_c,
                                     check_bit_exact=(n_c == 16))
    for spol, thr_ in (("ect", 0.05), ("mlml", 5.0), ("nltr", 5.0)):
        tuned_kernel_throughput(n_servers=100, n_trials=100, policy=spol,
                                threshold=thr_)
    tuned_kernel_throughput(n_servers=100, n_trials=100, n_clients=4)
    kernel_phase_profile_point(n_servers=100, n_trials=100)


if __name__ == "__main__":
    # --profile-trace [dir]: wrap the selected mode in a jax.profiler
    # trace (viewable with tensorboard/perfetto) — opt-in because trace
    # files are large and tracing perturbs the wall numbers
    _ctx = None
    if "--profile-trace" in sys.argv:
        _i = sys.argv.index("--profile-trace")
        _dir = (sys.argv[_i + 1]
                if len(sys.argv) > _i + 1
                and not sys.argv[_i + 1].startswith("--")
                else os.path.join(_REPO_ROOT, "profile_trace"))
        import jax
        _ctx = jax.profiler.trace(_dir)
        print(f"[sched_perf] jax.profiler trace -> {_dir}")
        _ctx.__enter__()
    try:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        if "--sharded-worker" in sys.argv:
            _sharded_worker(
                json.loads(sys.argv[sys.argv.index("--sharded-worker") + 1]))
        elif "--smoke" in sys.argv:
            run_smoke()
        elif "--trajectory" in sys.argv:
            trajectory()
        else:
            if _forced_host_devices() and _sharded_devices_requested():
                # the CPU workers start before this process touches JAX;
                # emit_bench_point's identical call reuses the result
                sharded_sweep_throughput(
                    n_servers=100, n_trials=100,
                    devices=_sharded_devices_requested())
            run_all()
            emit_bench_point()
    finally:
        if _ctx is not None:
            _ctx.__exit__(None, None, None)
