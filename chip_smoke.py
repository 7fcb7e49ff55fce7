#!/usr/bin/env python3
"""Chip smoke test: the scheduler's main path, compiled for the TPU, at
the paper's §4 scale (100 servers, 2,000 requests, window 100, 100
trials).

Run from the root of the checkout on a machine with a TPU::

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # the sharded sweep on four chips

One chip.  Every phase drives ``simulate.run_trials`` — the user's entry
point — with ``backend="kernel"`` (the whole Monte-Carlo sweep as one
Pallas kernel, compiled by Mosaic: the lowered program must hold a
``tpu_custom_call``), then with ``backend="jax"`` (the lax.scan engine)
on the same key as its reference:

* ``shared_log``, scenario ``transient``, each of ``SWEEP_POLICIES``;
* ``per_client``, ``ect``, at 64 clients and at the default 200.

``chosen`` must equal the reference exactly; ``latencies`` and
``phase_time`` must be bitwise equal, or within ``ULP_TOL`` units in the
last place (the largest gap is printed either way).  ``--chips 4`` runs
only the sharded sweep (``SimConfig.mesh_shape``): ``(4,)`` for
shared_log and ``(2, 2)`` for per_client at 64 clients, each against
``mesh_shape=None`` in the same process, bit-exact.

Lines before the last are informational (shapes, compile seconds, one
timed run, equality flags) — host-clock numbers, not device metrics.
The last line is one JSON object: ``{"ok": true, "device": {...}}``.
The script exits non-zero, and prints no such line, if JAX finds no TPU
or any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Largest gap, in units in the last place, admitted between the kernel's
# and the engine's latencies/phase_time.  Decisions are never compared
# with a tolerance.
ULP_TOL = 0

PAPER = dict(n_servers=100, n_requests=2000, window_size=100,
             n_trials=100, tiles="default")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def max_ulp_gap(a, b) -> int:
    """Largest distance between two f32 arrays in units in the last
    place (0 iff bitwise equal, treating +0 and -0 as equal)."""
    def line(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32)
        i = i.astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    if np.shape(a) != np.shape(b):
        raise ValueError(f"shape mismatch {np.shape(a)} vs {np.shape(b)}")
    return int(np.max(np.abs(line(a) - line(b)), initial=0))


def _run(simulate, key, cfg, pol, log_cfg):
    """Lower, compile and run ``run_trials`` once; returns (result,
    lowered text, compiled text, compile seconds, run seconds)."""
    import jax
    t0 = time.perf_counter()
    lowered = simulate.run_trials.lower(key, cfg, pol, log_cfg)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(key))
    run_s = time.perf_counter() - t0
    out = jax.tree.map(np.asarray, out)
    return out, lowered.as_text(), compiled.as_text(), compile_s, run_s


def _check_outputs(name, res, cfg):
    t, r = cfg.n_trials, cfg.n_requests
    if res.chosen.shape != (t, r) or res.latencies.shape != (t, r) \
            or res.phase_time.shape != (t,):
        raise AssertionError(
            f"{name}: shapes chosen {res.chosen.shape} latencies "
            f"{res.latencies.shape} phase_time {res.phase_time.shape}, "
            f"expected ({t}, {r}) / ({t},)")
    for f in ("latencies", "phase_time"):
        if not np.isfinite(getattr(res, f)).all():
            raise AssertionError(f"{name}: non-finite {f}")
    if not ((res.chosen >= 0) & (res.chosen < cfg.n_servers)).all():
        raise AssertionError(f"{name}: server id out of range")


def one_chip_phases(jax, simulate, PolicyConfig):
    from repro.core.simulate import ScenarioConfig, SimConfig
    key = jax.random.key(0)
    transient = ScenarioConfig(name="transient")
    phases = [(f"shared_log/{p}", p, dict(client_model="shared_log"))
              for p in simulate.SWEEP_POLICIES]
    phases += [(f"per_client/ect/{c}c", "ect",
                dict(client_model="per_client", n_clients=c))
               for c in (64, 200)]
    for name, policy, extra in phases:
        cfg = SimConfig(backend="kernel", scenario=transient,
                        **PAPER, **extra)
        pol = PolicyConfig(name=policy,
                           threshold=0.05 if policy == "ect" else 5.0,
                           rng="lcg")
        log_cfg = simulate.default_log_cfg(cfg)
        ker, ker_text, _, ker_c, ker_r = _run(simulate, key, cfg, pol,
                                              log_cfg)
        if "tpu_custom_call" not in ker_text:
            raise AssertionError(f"{name}: the kernel phase lowered "
                                 "without a tpu_custom_call")
        _check_outputs(name, ker, cfg)
        ref_cfg = dataclasses.replace(cfg, backend="jax")
        ref, _, _, ref_c, ref_r = _run(simulate, key, ref_cfg, pol, log_cfg)
        _check_outputs(name + " (reference)", ref, ref_cfg)
        chosen_eq = bool(np.array_equal(ker.chosen, ref.chosen))
        lat_gap = max_ulp_gap(ker.latencies, ref.latencies)
        phase_gap = max_ulp_gap(ker.phase_time, ref.phase_time)
        fields_eq = {f: bool(np.array_equal(getattr(ker, f), getattr(ref, f)))
                     for f in ker._fields}
        print(json.dumps({
            "phase": name, "policy": policy,
            "client_model": cfg.client_model,
            "n_clients": cfg.n_clients if cfg.client_model == "per_client"
            else None,
            "shapes": {"chosen": list(ker.chosen.shape),
                       "window_loads": list(ker.window_loads.shape)},
            "kernel_compile_s": ker_c, "kernel_run_s": ker_r,
            "jax_compile_s": ref_c, "jax_run_s": ref_r,
            "tpu_custom_call": True,
            "chosen_equal": chosen_eq,
            "latencies_max_ulp": lat_gap, "phase_time_max_ulp": phase_gap,
            "fields_bitwise_equal": fields_eq}), flush=True)
        if not chosen_eq:
            n_diff = int(np.sum(ker.chosen != ref.chosen))
            raise AssertionError(f"{name}: {n_diff} decisions differ from "
                                 "the backend='jax' reference")
        if lat_gap > ULP_TOL or phase_gap > ULP_TOL:
            raise AssertionError(
                f"{name}: latencies differ by {lat_gap} ulp, phase_time by "
                f"{phase_gap} ulp (ULP_TOL={ULP_TOL})")


def four_chip_phases(jax, simulate, PolicyConfig):
    from repro.core.simulate import ScenarioConfig, SimConfig
    from repro.launch.mesh import make_sweep_mesh
    if jax.device_count() < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                         f"{jax.device_count()}")
    key = jax.random.key(0)
    pol = PolicyConfig(name="ect", threshold=0.05, rng="lcg")
    cases = [("shared_log", (4,), dict(client_model="shared_log"), ()),
             ("per_client/64c", (2, 2),
              dict(client_model="per_client", n_clients=64),
              ("all-gather", "all-reduce"))]
    for name, mesh_shape, extra, needed in cases:
        cfg = SimConfig(backend="kernel",
                        scenario=ScenarioConfig(name="transient"),
                        **PAPER, **extra)
        log_cfg = simulate.default_log_cfg(cfg)
        mesh = make_sweep_mesh(mesh_shape)
        ids = [d.id for d in mesh.devices.flat]
        single, _, _, s_c, s_r = _run(simulate, key, cfg, pol, log_cfg)
        sh_cfg = dataclasses.replace(cfg, mesh_shape=mesh_shape)
        sharded, sh_text, sh_comp, m_c, m_r = _run(simulate, key, sh_cfg,
                                                   pol, log_cfg)
        _check_outputs(name, sharded, sh_cfg)
        present = [c for c in COLLECTIVES if c in sh_comp]
        fields_eq = {f: bool(np.array_equal(getattr(single, f),
                                            getattr(sharded, f)))
                     for f in single._fields}
        print(json.dumps({
            "phase": f"sharded/{name}", "mesh_shape": list(mesh_shape),
            "mesh_device_ids": ids,
            "mesh_distinct_devices": len(set(ids)),
            "tpu_custom_call": "tpu_custom_call" in sh_text,
            "collectives": present,
            "expected_collectives_present": all(c in present
                                                for c in needed),
            "single_compile_s": s_c, "single_run_s": s_r,
            "sharded_compile_s": m_c, "sharded_run_s": m_r,
            "fields_bitwise_equal": fields_eq}), flush=True)
        if len(set(ids)) != 4:
            raise AssertionError(f"{name}: mesh spans devices {ids}")
        if "tpu_custom_call" not in sh_text:
            raise AssertionError(f"{name}: no tpu_custom_call")
        if not all(c in present for c in needed):
            raise AssertionError(f"{name}: compiled program holds "
                                 f"{present}, expected {list(needed)}")
        if not all(fields_eq.values()):
            raise AssertionError(f"{name}: sharded != single-device: "
                                 f"{fields_eq}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "sweep against the single-device dispatch")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this test runs only on the chip", file=sys.stderr)
        return 2
    from repro.core import simulate
    from repro.core.policies import PolicyConfig
    print(json.dumps({"jax": jax.__version__, "compile_cache": cache_dir,
                      "platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}), flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phases(jax, simulate, PolicyConfig)
    else:
        one_chip_phases(jax, simulate, PolicyConfig)
    print(json.dumps({"total_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
