"""Stage scopes + the differential kernel phase profiler.

Two layers (DESIGN.md §16):

* STAGE SCOPES — `simulate.run_trials`, `simulate._run_batched`,
  `engine.run_stream_batch`, the `kernels.sched_select.ops` grid
  wrappers and `parallel.sweep.run_sweep` wrap their pipeline stages in
  :func:`stage`, a ``jax.named_scope``.  A name scope is metadata at
  trace time: every compiled op carries its stage path in its
  ``op_name`` (``jit(run_trials)/sched/book/...``), the compiled
  program is otherwise the same, and nothing runs at run time, so there
  is no switch.  The stages are read back from the device trace: the
  benchmark's ``bench/stagetrace.py`` sums each device op's time under
  the innermost leaf stage of its path (:data:`STAGES`).  The stages,
  outermost first::

      prep              trial keys, workloads, loads, traces
      sched             parent of the four below
        engine_prep     client and window split, step grouping, seeds,
                        per-window rates
        kernel          the kernel wrapper: pads, casts, the
                        pallas_call, its slices
        merge           the per_client cross-client merge (the grid
                        wrapper's, inside ``kernel``, and the fold after)
        book            the kernel-path bookkeeping
      post              the TrialResult stack

* KERNEL PHASE PROFILER — :func:`kernel_phase_profile` attributes the
  trial-grid kernel's wall time to its window phases by DIFFERENTIAL
  timing over the kernel's cumulative ``ablate`` levels (0 = full, 1 =
  no fused metrics, 2 = also no step loop, 3 = also no sort/plan):
  ``metrics_s = t0 - t1``, ``steps_s = t1 - t2``, ``plan_s = t2 - t3``
  and ``dispatch_s = t3`` (grid dispatch + per-window renorm/drain
  bookkeeping — the interpret-mode floor).  A clock inside the fused
  kernel body is impossible (and banned by CC-TIME), so ablation is the
  only honest per-phase attribution.  Timing lives HERE, not in the
  engine files — the scheduling surface is under the `contractcheck`
  CC-TIME rule (no clocks near the contract code).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax

# The leaf stages, outermost first: every device op of a sweep that is
# not the kernel or a collective falls under exactly one of them (or
# under the bare ``sched`` parent).  The benchmark keeps a copy of this
# tuple (it imports nothing of the program); a test holds the two equal.
STAGES = ("prep", "engine_prep", "kernel", "merge", "book", "post")
PARENTS = ("sched",)


def stage(name: str):
    """``jax.named_scope(name)`` for a stage of :data:`STAGES` or a
    parent of :data:`PARENTS`."""
    if name not in STAGES + PARENTS:
        raise ValueError(f"stage {name!r} is not one of {STAGES + PARENTS}")
    return jax.named_scope(name)


def median_time(run: Callable[[], object], reps: int = 3) -> float:
    """Median wall seconds of ``run()`` over ``reps`` timed calls after
    one untimed warmup (compile + cache)."""
    jax.block_until_ready(run())
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def best_time(run: Callable[[], object], reps: int = 3) -> float:
    """Best (minimum) wall seconds of ``run()`` over ``reps`` timed
    calls after one untimed warmup.  The workload is deterministic, so
    every rep above the minimum is measurement noise (scheduler
    preemption, cache pollution from a neighbouring stage); on a busy
    single-core container one such spike under a median flips candidate
    winners between tuning runs, while the minimum stays stable."""
    jax.block_until_ready(run())
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best


def kernel_phase_profile(*, n_servers: int = 100, n_requests: int = 2000,
                         window_size: int = 100, n_trials: int = 100,
                         policy: str = "ect", threshold: float = 0.05,
                         trial_tile: Optional[int] = None, reps: int = 3,
                         seed: int = 0) -> Dict[str, float]:
    """Per-window-phase wall-time attribution of the trial-grid kernel
    (differential over ``ablate`` levels; see module docstring).

    Returns ``{"total_s", "metrics_s", "steps_s", "plan_s",
    "dispatch_s"}`` — the last four are clamped nonnegative and the
    deltas are taken on one shared prep, so engine-side dispatch costs
    cancel out of every phase except the ``dispatch_s`` floor."""
    from repro.core import simulate
    from repro.core.policies import PolicyConfig
    from repro.core.statlog import LogConfig

    cfg = simulate.SimConfig(n_servers=n_servers, n_requests=n_requests,
                             window_size=window_size, n_trials=n_trials,
                             backend="kernel", trial_tile=trial_tile)
    pol = PolicyConfig(name=policy, threshold=threshold, rng="lcg")
    log_cfg = LogConfig(n_servers=n_servers,
                        lam=simulate.default_log_cfg(cfg).lam)
    keys = jax.random.split(jax.random.key(seed), n_trials)
    prep_jit = jax.jit(simulate._prep_trials, static_argnums=(1, 2))
    _, _, works, states, traces, k_sched = jax.block_until_ready(
        prep_jit(keys, cfg, log_cfg))

    from repro.core import engine

    def runner(level: int) -> Callable[[], object]:
        fn = jax.jit(lambda st, w, k: engine.run_stream_batch(
            st, w, k, policy=pol, log_cfg=log_cfg,
            window_size=cfg.window_size, group_steps=True, traces=traces,
            window_dt=0.0, observe=False, trial_tile=cfg.trial_tile,
            ablate=level))
        return lambda: fn(states, works, k_sched)

    t = [median_time(runner(level), reps=reps) for level in range(4)]
    return {
        "total_s": t[0],
        "metrics_s": max(t[0] - t[1], 0.0),
        "steps_s": max(t[1] - t[2], 0.0),
        "plan_s": max(t[2] - t[3], 0.0),
        "dispatch_s": t[3],
    }
