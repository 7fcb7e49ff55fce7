"""The sweep's stages on the device trace: what ``xla_ops_ms`` is made of.

The program names its pipeline stages with ``jax.named_scope``
(``repro.tune.profile.stage``), so every compiled op carries its stage
path in its ``op_name``: ``jit(run_trials)/sched/book/vmap(...)/gather``.
:data:`STAGES` is a copy of the program's leaf stages (the benchmark
imports nothing of the program to read it; a test holds the two equal).

The harness hands its readers the reduced window, not the trace, so the
stage readers take a trace of their own: :func:`record` runs
:data:`N_SWEEPS` more closed-loop sweeps of the cell's program under the
profiler once the window has closed, with the window's host spans
(``key``, ``dispatch``, ``block``).  :func:`load_events` keeps, per
chip, every device op with its scope path and the program executions
(the device plane's ``XLA Modules`` line), and the benchmark's host
spans with JAX's own host events nested in ``dispatch``.  :func:`reduce`
splits the device time of every op that is not the kernel or a
collective (what ``xla_ops_ms`` counts) by the innermost leaf stage of
its path: ``sched_other`` for ops under ``sched`` with no leaf,
``unscoped`` for ops with no stage at all.  It also places the longest
idle gaps between the ops and programs on either side, and totals the
host events inside ``dispatch``.  Both are plain functions of their
input, so recorded traces (``tests/data``) pin them.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import shutil

from bench import devtrace

STAGES = ("prep", "engine_prep", "kernel", "merge", "book", "post")
PARENTS = ("sched",)
OTHER = "sched_other"      # under ``sched``, under none of its leaves
UNSCOPED = "unscoped"      # under no stage: XLA's own copies, and the
                           # ops of other programs (the key's fold_in)
N_SWEEPS = 6
SEED = 2 ** 31 + 1013
MODULE_LINE = "XLA Modules"
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "_trace", "stages")
_WRAP = re.compile(r"^[\w.\-]*\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_CACHE: dict = {}


def stage_of(path: str) -> str:
    """The innermost leaf stage of an ``op_name`` scope path.

    Transformations wrap the scopes they hold (``vmap(book)``,
    ``jit(f)``), so each segment is unwrapped before it is matched."""
    found = []
    for seg in path.split("/"):
        m = _WRAP.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAP.match(seg)
        if seg in STAGES or seg in PARENTS:
            found.append(seg)
    leaves = [s for s in found if s in STAGES]
    if leaves:
        return leaves[-1]
    return OTHER if found else UNSCOPED


def program_op_names(hlo_text: str) -> tuple:
    """``(module, {instruction: scope path})`` of a compiled program's
    text (``compiled.as_text()``); instruction names are unique in a
    module.

    An instruction's path is its own ``op_name`` when that names a
    stage.  XLA builds some instructions with no ``op_name`` of their
    own (a scatter fusion, for one); such an instruction takes the first
    staged ``op_name`` of the computations it calls, searched from their
    roots down, which is the program op it was built from (a scatter's
    reducer)."""
    module = re.search(r"^HloModule ([\w.\-]+)", hlo_text, re.M)
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            cur = m.group(1) if m else None
            comps.setdefault(cur, [])
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            comps[cur].append((m.group(1), op.group(1) if op else "",
                               _CALLED.findall(line)))
    found = {}

    def staged(comp):
        """The first staged op_name in ``comp``, root first."""
        if comp not in found:
            found[comp] = ""          # a computation that calls itself
            for _, op, called in reversed(comps.get(comp, ())):
                op = op if stage_of(op) != UNSCOPED else \
                    next(filter(None, map(staged, called)), "")
                if op:
                    found[comp] = op
                    break
        return found[comp]

    names = {}
    for instrs in comps.values():
        for name, op, called in instrs:
            if stage_of(op) == UNSCOPED:
                op = next(filter(None, map(staged, called)), op)
            names[name] = op
    return (module.group(1) if module else ""), names


def load_events(trace_dir: str, program: tuple) -> dict:
    """``{"devices": {id: [[op, start_ns, dur_ns, scope_path], ...]},
    "modules": {id: [[program, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...],
    "host_events": [[name, start_ns, dur_ns], ...]}`` from the newest
    trace under ``trace_dir``.

    The trace names each device op, but its ``op_name`` sits in event
    metadata that ``jax.profiler.ProfileData`` does not read.  So the
    scope path comes from ``program``, :func:`program_op_names` of the
    compiled program: an op that ran inside an execution of that program
    gets its instruction's path, any other op ''.  ``host_events`` are
    the host events that a ``dispatch`` span of the same thread holds."""
    import glob

    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, modules, host, inner = {}, {}, [], []
    for plane in data.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            ops, runs = [], []
            for line in plane.lines:
                if line.name == devtrace.OP_LINE:
                    ops.extend([devtrace.op_name(e.name), e.start_ns,
                                e.duration_ns] for e in line.events)
                elif line.name == MODULE_LINE:
                    runs.extend([e.name, e.start_ns, e.duration_ns]
                                for e in line.events)
            devices[dev], modules[dev] = _scoped(ops, runs, *program), runs
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                spans = [ev for ev in events if ev[0] == "dispatch"]
                host.extend(ev for ev in events
                            if ev[0] in devtrace.HOST_SPANS)
                inner.extend(_nested(events, spans))
    return {"devices": devices, "modules": modules, "host": host,
            "host_events": inner}


def _scoped(ops, runs, module, op_names):
    """``ops`` with the scope path of each: ``op_names[op]`` for an op
    inside an execution of ``module`` (a run of the ``XLA Modules`` line
    whose name starts with the module's), '' for any other."""
    runs = sorted((s, s + d, bool(module) and name.startswith(module))
                  for name, s, d in runs)
    starts = [s for s, _, _ in runs]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < runs[i][1] and runs[i][2]
        out.append([name, s, d, op_names.get(name, "") if inside else ""])
    return out


def _nested(events, spans):
    """The events held by one of ``spans`` (the spans themselves left
    out), all on one thread."""
    spans = sorted((s, s + d) for _, s, d in spans)
    starts = [s for s, _ in spans]
    out = []
    for name, s, d in events:
        if name == "dispatch":
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= spans[i][1]:
            out.append([name, s, d])
    return out


def reduce(events: dict, n_devices: int, top: int = 10) -> dict:
    """Stage split, gap sites and dispatch host events of a trace.

    The window and the clipping are ``devtrace.reduce``'s: from the
    first host span to the end of the last, over the first ``n_devices``
    chips; times are means over those chips, op counts their sums.
    ``stage_ns``/``stage_events`` hold every op that is not the kernel
    or a collective, so ``stage_ns`` sums to ``xla_ns``, which is
    ``devtrace.reduce``'s ``other_ns`` of the same trace.  The gaps are
    chip 0's, as there."""
    host = sorted(events["host"], key=lambda h: h[1])
    if not host:
        raise ValueError("the trace holds none of the benchmark's host spans")
    w0 = host[0][1]
    w1 = max(s + d for _, s, d in host)
    ids = sorted(events["devices"])[:n_devices]
    n = max(len(ids), 1)
    stage_ns, stage_events = {}, {}
    gaps = []
    for dev in ids:
        ops = []
        for ev in events["devices"][dev]:
            name, s, d = ev[:3]
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            stage = stage_of(ev[3] if len(ev) > 3 else "")
            ops.append((name, s, e, stage))
            if not (devtrace.KERNEL.search(name)
                    or devtrace.COLLECTIVE.search(name)):
                stage_ns[stage] = stage_ns.get(stage, 0) + (e - s)
                stage_events[stage] = stage_events.get(stage, 0) + 1
        if dev == ids[0]:
            gaps = _gap_sites(ops, events.get("modules", {}).get(dev, []),
                              host, w0, w1)[:top]
    spans = [h for h in host if h[0] == "dispatch"]
    n_disp = max(len(spans), 1)
    per_name = {}
    for name, s, d in events.get("host_events", []):
        per_name.setdefault(name, []).append((s, s + d))
    # an event nested in one of its own name (a jitted call inside
    # another's dispatch) counts once
    dispatch = sorted(([k, sum(e - s for s, e in devtrace._union(v))
                        / n_disp / 1e6] for k, v in per_name.items()),
                      key=lambda kv: -kv[1])
    return {
        "n_devices": len(ids),
        "sweeps": len(spans),
        "xla_ns": sum(stage_ns.values()) / n,
        "stage_ns": {k: v / n for k, v in stage_ns.items()},
        "stage_events": stage_events,
        "gap_sites": gaps,
        "dispatch_ms": sum(d for _, _, d in spans) / n_disp / 1e6,
        "dispatch_events": dispatch[:5],
    }


def _gap_sites(ops, modules, host, w0, w1):
    """Every idle gap of one chip's clipped ``ops`` (``(name, start,
    end, stage)``), longest first: its length and start in ms, the host
    span over its middle, the ``[stage, op]`` that ends where it starts
    and the one that starts where it ends (None at the window's edge),
    and whether those two ran in the same program execution."""
    ops = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    by_end = sorted(ops, key=lambda o: o[2])
    ends = [o[2] for o in by_end]
    runs = sorted(modules, key=lambda r: r[1])
    run_starts = [r[1] for r in runs]
    host_starts = [s for _, s, _ in host]

    def run_of(op):
        mid = (op[1] + op[2]) // 2
        j = bisect.bisect_right(run_starts, mid) - 1
        return j if j >= 0 and mid < runs[j][1] + runs[j][2] else None

    merged = devtrace._union([(o[1], o[2]) for o in ops])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        i = bisect.bisect_right(ends, a) - 1
        pre = by_end[i] if i >= 0 else None
        j = bisect.bisect_left(starts, b)
        post = ops[j] if j < len(ops) else None
        same = (pre is not None and post is not None
                and run_of(pre) is not None and run_of(pre) == run_of(post))
        gaps.append({"ms": (b - a) / 1e6, "at_ms": (a - w0) / 1e6,
                     "host": devtrace._label(host, host_starts, (a + b) // 2),
                     "before": pre and [pre[3], pre[0]],
                     "after": post and [post[3], post[0]],
                     "same_program": same})
    gaps.sort(key=lambda g: -g["ms"])
    return gaps


def stage_ms(summary, stage: str):
    """Device time of ``stage`` per sweep, in ms, mean over the chips;
    None where the trace holds no op of it."""
    if not summary or not summary["sweeps"] \
            or not summary["stage_events"].get(stage):
        return None
    return summary["stage_ns"][stage] / summary["sweeps"] / 1e6


def record(cell, n_sweeps: int = N_SWEEPS, trace_dir: str = TRACE_DIR):
    """Events of ``n_sweeps`` closed-loop sweeps of ``cell``'s program
    under the profiler, after one sweep outside it, with the scope paths
    of the compiled program.  The program is the window's (the same
    configuration: nothing compiles anew, its text comes from the
    compile cache); the keys come from :data:`SEED`, as device time does
    not depend on them."""
    import jax

    from repro.core import simulate
    from repro.core.policies import PolicyConfig
    # the arguments ``run.program_sweep`` builds: equal static arguments,
    # so the same compiled program
    sim = dict(cell.config["sim"])
    mesh = sim.pop("mesh_shape")
    cfg = simulate.SimConfig(
        scenario=simulate.ScenarioConfig(**cell.traffic["scenario"]),
        mesh_shape=tuple(mesh) if mesh else None, **sim)
    args = (cfg, PolicyConfig(**cell.traffic["policy"]),
            simulate.default_log_cfg(cfg))
    base = jax.random.key(SEED)
    program = program_op_names(
        simulate.run_trials.lower(base, *args).compile().as_text())

    def sweep(key):
        return simulate.run_trials(key, *args)

    annotate = jax.profiler.TraceAnnotation
    jax.block_until_ready(sweep(jax.random.fold_in(base, 0)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for i in range(1, n_sweeps + 1):
            with annotate("key"):
                key = jax.random.fold_in(base, i)
            with annotate("dispatch"):
                out = sweep(key)
            with annotate("block"):
                jax.block_until_ready(out)
            out = None
    finally:
        jax.profiler.stop_trace()
    try:
        return load_events(trace_dir, program)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def summary(ctx) -> dict:
    """The reduced stage trace of the cell in ``ctx`` (the readers'
    context), recorded once per process; the first call also prints it
    as an info line on stdout."""
    cell = ctx["cell"]
    if cell.name not in _CACHE:
        s = reduce(record(cell), n_devices=ctx["summary"]["n_devices"])
        _CACHE[cell.name] = s
        print(json.dumps(info_line(s, cell.name)), flush=True)
    return _CACHE[cell.name]


def info_line(s: dict, cell_name: str) -> dict:
    """The ``stages`` info line: per-sweep ms of each stage and of their
    sum, op counts, gap sites and dispatch host events."""
    import jax
    devices = jax.devices()
    sweeps = max(s["sweeps"], 1)
    return {"info": "stages", "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "workload": cell_name, "sweeps": s["sweeps"],
            "xla_ops_ms": s["xla_ns"] / sweeps / 1e6,
            "stage_ms": {k: v / sweeps / 1e6
                         for k, v in sorted(s["stage_ns"].items())},
            "stage_events": dict(sorted(s["stage_events"].items())),
            "gap_sites": s["gap_sites"],
            "dispatch_ms": s["dispatch_ms"],
            "dispatch_host_ms": s["dispatch_events"]}
