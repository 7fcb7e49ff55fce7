#!/usr/bin/env python3
"""Benchmark of the paper-scale Monte-Carlo policy sweep on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is one deployment
(``configs/<config>.json``) under one traffic mix (``traffic/<traffic>.json``).
The run drives the user's entry point, ``repro.core.simulate.run_trials``
with ``backend="kernel"``, as a closed loop with one sweep in flight: sweep
``i`` gets the key ``fold_in(key(seed), i)``, ends in ``block_until_ready``,
and only then is the next one dispatched.

* Set-up (``setup_s``): process start to the start of the window — imports,
  device init, the compile of the cell's one program (from the persistent
  cache in ``<checkout>/.jax_cache`` after the first run) and two warm-up
  sweeps.
* Window: ``--seconds`` of sweeps.  ``decisions_per_s`` is every decision
  of every sweep completed in it over its length; ``sweep_ms_p95`` the
  nearest-rank 95th percentile of all its sweep times (call to
  ``block_until_ready``).
* Check: a uniform sample of the window's sweeps, drawn from the seed while
  the window runs (only their outputs are held), is compared with the
  plain reference (``reference.py``, ``check.py``) once the window has
  closed and the peak memory is read.
* ``--trace 1``: the same run under the profiler; the line then carries the
  per-layer metrics, read by ``metrics/<name>.py`` from the trace.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``compared`` last); the last lines of standard error give each
compared number beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench", "_trace")
N_CHECKED = 4        # sweeps of the window compared with the reference
N_WARMUP = 2         # sweeps before the window (keys 0 and 1)


def _say(tag: str, dev: dict, **kw) -> None:
    """One informational line on stdout, naming the device."""
    print(json.dumps({"info": tag, "platform": dev["platform"],
                      "kind": dev["kind"], "count": dev["count"], **kw}),
          flush=True)


def _p95(xs):
    """Nearest-rank 95th percentile."""
    s = sorted(xs)
    return s[max(math.ceil(0.95 * len(s)) - 1, 0)]


class CompileLog:
    """Counts compiles and persistent-cache hits through jax.monitoring."""

    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def program_sweep(cell: spec.Cell):
    """``key -> TrialResult`` of the program under test, for this cell."""
    from repro.core import simulate
    from repro.core.policies import PolicyConfig
    sim = dict(cell.config["sim"])
    mesh = sim.pop("mesh_shape")
    cfg = simulate.SimConfig(
        scenario=simulate.ScenarioConfig(**cell.traffic["scenario"]),
        mesh_shape=tuple(mesh) if mesh else None, **sim)
    pol = PolicyConfig(**cell.traffic["policy"])
    log_cfg = simulate.default_log_cfg(cfg)
    return lambda key: simulate.run_trials(key, cfg, pol, log_cfg)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             sweep, jax, dev: dict, peaks: dict, t_start: float,
             compile_log=None, err=sys.stderr) -> dict:
    """Set-up, window, check; returns the result object."""
    import numpy as np
    from bench import devtrace, reference

    sh = reference.shape_from(cell.config, cell.traffic)
    decisions = sh.n_trials * sh.n_requests
    base = jax.random.key(seed)
    annotate = jax.profiler.TraceAnnotation

    t_ready = time.perf_counter()
    warm = []
    for i in range(N_WARMUP):
        jax.block_until_ready(sweep(jax.random.fold_in(base, i)))
        warm.append(time.perf_counter())
    setup_s = time.perf_counter() - t_start
    compiles_setup = compile_log.compiles if compile_log else 0
    if compile_log:
        _say("setup", dev, setup_s=setup_s,
             start_to_device_s=t_ready - t_start,
             first_sweep_s=warm[0] - t_ready,
             second_sweep_s=warm[1] - warm[0], compiles=compile_log.compiles,
             compile_s=compile_log.compile_s, cache_hits=compile_log.hits,
             cache_misses=compile_log.misses, cache_dir=CACHE_DIR)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    # The checked sweeps: a uniform sample of the window's sweeps, drawn
    # from the seed as the window runs (reservoir sampling), so that only
    # those outputs are held and every other is dropped as it completes.
    rng = np.random.default_rng(seed)
    kept, layouts, times, failed, attempted = [], {}, [], 0, 0
    i = N_WARMUP
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < seconds:
        attempted += 1
        try:
            with annotate("key"):
                key = jax.random.fold_in(base, i)
            t0 = time.perf_counter()
            with annotate("dispatch"):
                out = sweep(key)
            with annotate("block"):
                jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
            fields = out._asdict() if hasattr(out, "_asdict") else dict(out)
            layout = tuple((k, tuple(np.shape(v))) for k, v in fields.items())
            layouts[layout] = layouts.get(layout, 0) + 1
            n = len(times)
            j = n - 1 if n <= N_CHECKED else int(rng.integers(n))
            if j < N_CHECKED:
                kept[j:j + 1] = [(i, layout, fields)]
        except Exception as e:  # a sweep that raises counts as failed
            failed += 1
            print(f"sweep {i} raised {type(e).__name__}: {e}", file=err)
        i += 1
        out = fields = None
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    in_window = (compile_log.compiles - compiles_setup) if compile_log else 0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev["devices"])

    # the check: the sampled sweeps, against the reference
    shapes = jax.eval_shape(lambda k: reference.simulate(
        reference.sweep_inputs(k, sh), sh), base)
    shapes = {k: v.shape for k, v in shapes.items()}
    bad = {}
    for layout, count in layouts.items():
        bad[layout] = check.malformed(
            {k: jax.ShapeDtypeStruct(s, np.float32) for k, s in layout},
            shapes)
        if bad[layout]:
            failed += count
            print(f"{count} sweep(s) malformed: {bad[layout]}", file=err)
    sample = sorted((idx, {k: np.asarray(v) for k, v in fields.items()})
                    for idx, layout, fields in kept if not bad[layout])
    kept.clear()
    ref_fn = reference.reference_fn(sh, forced=True)
    parts = []
    for idx, prog in sample:
        ref = reference.to_numpy(ref_fn(jax.random.fold_in(base, idx),
                                        prog["chosen"]))
        parts.append(check.compare(prog, ref))
    numbers = check.combine(parts) if parts else {
        "wrong_answers": 1.0, "value_gap": check.NEVER, "wrong": 0,
        "answers": 0, "gap_field": "nothing checked"}
    correct = bool(parts) and failed == 0 and check.verdict(numbers,
                                                            cell.limits)

    n_ok = len(times)
    _say("window", dev, sweeps=n_ok, attempted=attempted, failed=failed,
         window_s=window_s, sweep_ms_median=float(np.median(times)) * 1e3
         if times else None, sweep_ms_samples=n_ok,
         compiles_in_window=in_window, checked_sweeps=[s[0] for s in sample],
         wrong=numbers["wrong"], answers=numbers["answers"],
         gap_field=numbers["gap_field"])

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        events = devtrace.load_events(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        summary = devtrace.reduce(events, n_devices=len(dev["devices"]))
        _say("trace", dev, longest_gaps_at_s=summary["gaps_at_s"],
             host_spans=summary["host_spans"])
        ctx = dict(summary=summary, sweeps=n_ok, shape=sh, peaks=peaks,
                   cell=cell)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        values = {
            "decisions_per_s": n_ok * decisions / window_s if n_ok else 0.0,
            "sweep_ms_p95": (_p95(times) if times else window_s) * 1e3,
            "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["compared"] = {n: {"value": numbers[n], "limit": cell.limits[n]}
                          for n in check.NUMBERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except LookupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    # the program's compile cache: a fixed directory inside the checkout,
    # handed to the program through the variable it reads
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}, "
              f"{len(devices)} device(s)); this benchmark runs only on the "
              "chip", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)} ({devices[0].device_kind})", file=sys.stderr)
        return 3
    kind = devices[0].device_kind
    try:
        peaks = spec.peaks(kind)
    except LookupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_log = CompileLog(jax)
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "devices": devices[:cell.chips]}

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      program_sweep(cell), jax, dev, peaks, T_START,
                      compile_log=compile_log)
    tag = f"[{dev['platform']} {kind} x{dev['count']}]"
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r} {tag}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
