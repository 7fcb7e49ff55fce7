#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/readings.py --workload <cell> [--seeds 12]
        [--control-seeds 3] [--out DIR]

In one process on the chip, at the cell's own size: for each program seed,
one sweep of the timed path (``run_trials`` with the cell's configuration,
key ``fold_in(key(seed), 2)``, the first key of a window) compared with the
plain reference; for each control seed, the reference itself in bfloat16
put in the program's place and compared the same way.  The lower reading of
a number is the largest over the program's seeds, the upper the smallest
over the control's.  Prints one JSON line per seed and a summary last,
and with ``--out`` writes the same to ``<DIR>/<cell>.json``.  The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, run, spec  # noqa: E402

SEED0 = 3_000_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", help="directory for <cell>.json")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import reference
    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    dev = jax.devices()[0]
    sh = reference.shape_from(cell.config, cell.traffic)
    forced = reference.reference_fn(sh, forced=True)
    control = reference.reference_fn(sh, forced=False, dtype=jnp.bfloat16)
    sweep = run.program_sweep(cell)
    rows = []

    def record(kind, seed, prog, t_ref):
        key = jax.random.fold_in(jax.random.key(seed), run.N_WARMUP)
        t0 = time.perf_counter()
        ref = reference.to_numpy(forced(key, prog["chosen"]))
        c = check.compare(prog, ref)
        row = {"kind": kind, "seed": seed,
               "wrong_answers": c["wrong"] / c["answers"],
               "value_gap": c["value_gap"], "gap_field": c["gap_field"],
               "wrong": c["wrong"], "answers": c["answers"],
               "run_s": t_ref, "reference_s": time.perf_counter() - t0,
               "kind_of_device": dev.device_kind}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for k in range(args.seeds):
        seed = SEED0 + k
        key = jax.random.fold_in(jax.random.key(seed), run.N_WARMUP)
        t0 = time.perf_counter()
        out = jax.block_until_ready(sweep(key))
        prog = {f: np.asarray(v) for f, v in out._asdict().items()}
        record("program", seed, prog, time.perf_counter() - t0)
    for k in range(args.control_seeds):
        seed = SEED0 + 1000 + k
        key = jax.random.fold_in(jax.random.key(seed), run.N_WARMUP)
        t0 = time.perf_counter()
        out = reference.to_numpy(control(key))
        record("control", seed, out, time.perf_counter() - t0)

    summary = {"workload": cell.name, "device": dev.device_kind,
               "limits_now": cell.limits}
    for n in check.NUMBERS:
        prog = [r[n] for r in rows if r["kind"] == "program"]
        ctrl = [r[n] for r in rows if r["kind"] == "control"]
        summary[n] = {"lower": max(prog) if prog else None,
                      "upper": min(ctrl) if ctrl else None,
                      "program": prog, "control": ctrl}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, cell.name + ".json"), "w") as fh:
            json.dump({"rows": rows, "summary": summary}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
