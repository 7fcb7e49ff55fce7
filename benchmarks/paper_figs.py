"""Paper §4 evaluation benchmarks — one function per table/figure.

Reproduces, at the paper's full scale (100 OSSs, 200 clients, 2,000
requests, 100 trials):

* Figs. 12-17 — per-OSS load distribution under RR / MLML / TRH / 1LTR /
  2LTR (CSV + ascii plot + balance stats table);
* Fig. 18     — straggler-injection experiment (10% of servers at 5x
  average load): max requests landed per load bucket, per policy;
* probe-message table — log-assisted policies vs the SC'14 two-choice
  baseline (§1/§5 claim: zero probes);
* nLTR n-sensitivity (n = 1, 2, 3) — §3.4.3 claim: n=2 suffices;
* I/O completion-time simulation on the queueing cluster (phase time with
  and without stragglers) — the end-metric the paper's balance serves.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analysis, simulate
from repro.core.engine import ClusterTrace
from repro.core.policies import PolicyConfig
from repro.core.simulate import ScenarioConfig, SimConfig
from repro.io import IOClient, IOClientConfig, SimulatedCluster

FULL = SimConfig()          # the paper's numbers: 100 OSS, 2000 reqs, 100 trials
QUICK = SimConfig(n_servers=50, n_requests=800, n_trials=12)


def _policies(threshold=5.0):
    return {
        "rr": PolicyConfig(name="rr"),
        "mlml": PolicyConfig(name="mlml", threshold=threshold),
        "trh": PolicyConfig(name="trh", threshold=threshold),
        "1ltr": PolicyConfig(name="nltr", threshold=threshold, nltr_n=1),
        "2ltr": PolicyConfig(name="nltr", threshold=threshold, nltr_n=2),
        "two_choice": PolicyConfig(name="two_choice", threshold=threshold),
    }


def figs_12_17(cfg: SimConfig = QUICK, plot: bool = True) -> Dict[str, dict]:
    """Headline summary per policy (Figs. 12-17 + latency promotion).

    The paper ranks by load balance; the quantity balance ultimately
    serves is request latency — so the summary LEADS with p99 latency
    and makespan (ROADMAP "latency metrics everywhere"), with the
    balance statistics as secondary columns.
    """
    log = simulate.default_log_cfg(cfg)
    key = jax.random.key(0)
    out = {}
    print("\n== Figs 12-17 headline: latency first, balance second "
          f"(M={cfg.n_servers}, R={cfg.n_requests}, T={cfg.n_trials}) ==")
    print(f"{'policy':>10s} {'p99_lat_s':>10s} {'makespan_s':>10s} "
          f"{'p50_lat_s':>10s} | {'cv':>7s} {'max':>10s} {'jain':>6s} "
          f"{'time_s':>7s}")
    for name, pol in _policies().items():
        t0 = time.time()
        res = simulate.run_trials(key, cfg, pol, log)
        jax.block_until_ready(res.server_loads)
        dt = time.time() - t0
        st = analysis.load_balance_stats(res.server_loads)
        ls = analysis.latency_stats(res.latencies)
        mk = analysis.makespan(res)
        out[name] = {"stats": st, "latency": ls, "makespan": mk,
                     "loads": analysis.mean_server_loads(res.server_loads)}
        print(f"{name:>10s} {ls['p99']:10.2f} {mk:10.2f} {ls['p50']:10.2f} "
              f"| {st['cv']:7.3f} {st['max']:10.1f} {st['jain']:6.3f} "
              f"{dt:7.2f}")
    if plot:
        for name in ("rr", "mlml", "trh"):
            print(analysis.ascii_plot(np.sort(out[name]["loads"]),
                                      label=f"Fig. sorted loads — {name}"))
    return out


def scenario_sweep_full(cfg: SimConfig = FULL) -> Dict[str, dict]:
    """ROADMAP item: the full-scale temporal scenario sweep — 100 OSS x
    2,000 requests x 100 trials per (scenario, policy) cell, all jitted.
    Ranks every sweep policy by p99 latency / makespan per scenario."""
    print(f"\n== FULL-scale scenario sweep (M={cfg.n_servers}, "
          f"R={cfg.n_requests}, T={cfg.n_trials}) ==")
    out = simulate.run_scenario_eval(seed=0, cfg=cfg)
    print(f"{'scenario':>16s} {'policy':>8s} {'p99_lat_s':>10s} "
          f"{'makespan_s':>10s} {'strag_hit%':>10s}")
    table: Dict[str, dict] = {}
    for scn, row in out.items():
        ranked = {}
        for pol, res in row.items():
            ls = analysis.latency_stats(res.latencies)
            ranked[pol] = {
                "p99": ls["p99"],
                "makespan": analysis.makespan(res),
                "hit": analysis.straggler_summary(res)["hit_fraction"],
            }
            print(f"{scn:>16s} {pol:>8s} {ranked[pol]['p99']:10.2f} "
                  f"{ranked[pol]['makespan']:10.2f} "
                  f"{100 * ranked[pol]['hit']:10.2f}")
        best = min(ranked, key=lambda p: ranked[p]["p99"])
        print(f"{'':>16s} best p99: {best}")
        table[scn] = ranked
    return table


def fig_18(cfg: SimConfig = None, plot: bool = True) -> Dict[str, dict]:
    """Straggler injection: 10% of OSSs at 5x average load (Fig. 18)."""
    cfg = cfg or SimConfig(n_servers=QUICK.n_servers,
                           n_requests=QUICK.n_requests,
                           n_trials=QUICK.n_trials,
                           straggler_frac=0.10, straggler_factor=5.0)
    log = simulate.default_log_cfg(cfg)
    key = jax.random.key(0)
    out = {}
    print("\n== Fig 18: straggler avoidance (10% stragglers @5x) ==")
    print(f"{'policy':>10s} {'strag_hit%':>10s} {'bytes->strag':>13s} "
          f"{'max_load':>10s} {'probes/req':>10s}")
    for name, pol in _policies().items():
        res = simulate.run_trials(key, cfg, pol, log)
        ss = analysis.straggler_summary(res)
        probes = float(np.asarray(res.probe_msgs).mean()) / cfg.n_requests
        out[name] = ss
        print(f"{name:>10s} {100*ss['hit_fraction']:10.2f} "
              f"{ss['mean_bytes_added_to_stragglers_mb']:13.1f} "
              f"{ss['max_load']:10.1f} {probes:10.2f}")
        xs, ys = analysis.fig18_curve(res.server_loads, res.n_assigned, 24)
        out[name]["curve"] = (xs, ys)
    if plot:
        for name in ("rr", "trh"):
            xs, ys = out[name]["curve"]
            print(analysis.ascii_plot(ys,
                                      label=f"Fig18 max-reqs vs load — {name}"))
    return out


def table_probe_overhead(cfg: SimConfig = QUICK) -> Dict[str, float]:
    """Probe messages per request (the cost the client-side log removes)."""
    log = simulate.default_log_cfg(cfg)
    out = simulate.run_paper_eval(
        seed=0, cfg=cfg,
        policy_names=("rr", "mlml", "trh", "nltr", "two_choice"))
    probes = analysis.probe_overhead(out, cfg.n_requests)
    print("\n== Probe-message overhead (per request) ==")
    for k, v in probes.items():
        print(f"{k:>10s} {v:8.3f}")
    return probes


def nltr_sensitivity(cfg: SimConfig = QUICK) -> Dict[int, float]:
    """nLTR n = 1, 2, 3 (§3.4.3: n=2 suffices; n=3 adds only overhead)."""
    log = simulate.default_log_cfg(cfg)
    key = jax.random.key(0)
    print("\n== nLTR n-sensitivity ==")
    out = {}
    for n in (1, 2, 3):
        t0 = time.time()
        res = simulate.run_trials(
            key, cfg, PolicyConfig(name="nltr", threshold=5.0, nltr_n=n),
            log)
        jax.block_until_ready(res.server_loads)
        cv = analysis.load_balance_stats(res.server_loads)["cv"]
        out[n] = cv
        print(f"  n={n} (K={2**n:2d}): cv={cv:.4f}  "
              f"wall={time.time()-t0:.2f}s")
    return out


def completion_time(n_servers: int = 24, n_files: int = 120,
                    file_mb: float = 16.0) -> Dict[str, float]:
    """End metric: synchronous I/O phase time on the queueing cluster with
    one slow-rate straggler + one pre-loaded server."""
    print("\n== Simulated I/O phase completion time (s) ==")
    out = {}
    for name in ("rr", "mlml", "trh", "nltr", "ect", "two_choice"):
        sim = SimulatedCluster(n_servers, base_rate_mb_s=200.0, seed=3)
        sim.make_straggler(1, 8.0)
        sim.add_external_load(1, 800.0)
        sim.add_external_load(5, 400.0)
        cli = IOClient(sim, IOClientConfig(
            policy=PolicyConfig(name=name, threshold=4.0)))
        for s in range(n_servers):  # client knows current queue depths
            cli.log.loads[s] = sim.queued_mb(s)
        for f in range(n_files):
            cli.write_file(f, size_mb=file_mb)
        phase = cli.flush()
        out[name] = phase
        print(f"{name:>10s} {phase:8.2f}s  straggler_hits="
              f"{sim.servers[1].n_requests:3d} probes={cli.probe_messages}")
    return out


def fig_temporal(n_trials: int = 12) -> Dict[str, dict]:
    """Beyond-paper temporal figure: time-varying stragglers.

    Left: jitted scenario sweep — straggler-hit fraction per window under
    the transient trace (does the policy track onset and recovery?) plus
    p99/makespan slowdown vs RR.  Right: the SAME ClusterTrace driven
    through the host-path queueing cluster (``SimulatedCluster(trace=)``)
    for one policy, cross-checking the two substrates.
    """
    cfg = SimConfig(n_servers=24, n_requests=480, n_trials=n_trials,
                    window_size=60,
                    scenario=ScenarioConfig(name="transient"))
    out = simulate.run_scenario_eval(
        seed=0, cfg=cfg, scenario_names=("transient",),
        policy_names=("rr", "trh", "ect"))["transient"]
    print("\n== Temporal (transient stragglers): hit-rate over time ==")
    for pol, res in out.items():
        hits = analysis.straggler_hits_over_time(
            res.chosen, res.straggler_mask, cfg.window_size)
        curve = " ".join(f"{100 * h:5.1f}" for h in hits)
        print(f"{pol:>6s} hit% per window: {curve}")
    slow = analysis.slowdown_vs_baseline(out, baseline="rr")
    print(f"{'policy':>8s} {'p99 vs rr':>10s} {'makespan vs rr':>15s}")
    for pol, s in slow.items():
        print(f"{pol:>8s} {s['p99_vs_rr']:10.2f} {s['makespan_vs_rr']:15.2f}")

    # host path on the same kind of trace: 2 servers flap slow mid-run
    m, base = 12, 200.0
    slow_row = np.full(m, base)
    slow_row[[1, 5]] = base / 8.0
    trace = ClusterTrace(times=jnp.asarray([0.0, 2.0, 6.0], jnp.float32),
                         rates=jnp.asarray(
                             np.stack([np.full(m, base), slow_row,
                                       np.full(m, base)]), jnp.float32))
    host = {}
    for pol, thr in (("rr", 0.0), ("trh", 4.0), ("ect", 0.05)):
        sim = SimulatedCluster(m, base_rate_mb_s=base, seed=3, trace=trace)
        cli = IOClient(sim, IOClientConfig(
            policy=PolicyConfig(name=pol, threshold=thr)))
        for f in range(48):
            cli.write_file(f, size_mb=16.0)
            sim.advance_time(0.25)          # writes spread over the trace
            for s in range(m):
                cli.log.loads[s] = sim.queued_mb(s)
        cli.flush()
        st = cli.stats()
        host[pol] = {"p99_write_s": st["p99_write_s"],
                     "done_at_s": sim.clock}
    print("host path, same transient trace: "
          + "  ".join(f"{p}: p99={h['p99_write_s']:.2f}s "
                      f"done@{h['done_at_s']:.1f}s" for p, h in host.items()))
    return {"sweep": out, "host": host}


def run_all(full: bool = False):
    cfg = FULL if full else QUICK
    figs_12_17(cfg)
    fig_18(SimConfig(n_servers=cfg.n_servers, n_requests=cfg.n_requests,
                     n_trials=cfg.n_trials, straggler_frac=0.10,
                     straggler_factor=5.0))
    table_probe_overhead(cfg)
    nltr_sensitivity(cfg)
    completion_time()
    fig_temporal()
    if full:
        # the paper-scale temporal sweep rides only on --full (it is the
        # single most expensive section: 5 scenarios x 5 policies x 100
        # jitted trials at 100 OSS / 2,000 requests)
        scenario_sweep_full()


if __name__ == "__main__":
    import sys
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run_all(full="--full" in sys.argv)
