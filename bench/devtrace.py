"""From a profiler trace to the numbers the per-layer readers take.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps what the reduction needs, in plain lists: every device op of every
chip (name, start, duration in ns) and the benchmark's own host spans
(``key``, ``dispatch``, ``block``).  ``reduce`` turns that into device
times by class (the sched_select kernel, collectives, every other op), the
busy union and idle share of the traced window, the ops that took most
time and the longest idle gaps, each labelled by the host span that
covers it.  Both are plain functions of their input, so a small recorded
trace (``tests/data``) pins them.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

HOST_SPANS = ("key", "dispatch", "block")
# The sched_select Pallas kernels carry no name of their own: their trace
# events are the custom calls named after the jitted wrapper that lowers
# them (``sched_stream_batch.1``, ``sched_stream_grid.1``).
KERNEL = re.compile(r"^sched_(stream|select)")
COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"


def load_events(trace_dir: str) -> dict:
    """``{"devices": {id: [[name, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}`` from the newest trace
    under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend([op_name(e.name), e.start_ns, e.duration_ns]
                               for e in line.events)
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """The HLO op of a device event: ``%fusion.8 = pred[...] ...`` ->
    ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, n_devices: int, top: int = 10) -> dict:
    """Device times, busy share and breakdown of a traced window.

    The window runs from the start of the first host span to the end of
    the last (the benchmark's own loop).  Device time is clipped to it;
    per-class times and busy seconds are means over the ``n_devices``
    chips of the cell (chips beyond those are ignored)."""
    host = sorted(events["host"], key=lambda h: h[1])
    if not host:
        raise ValueError("the trace holds none of the benchmark's host spans")
    w0 = host[0][1]
    w1 = max(s + d for _, s, d in host)
    spans = {}
    for name, _, dur in host:
        agg = spans.setdefault(name, {"n": 0, "total_ns": 0})
        agg["n"] += 1
        agg["total_ns"] += dur

    starts = [s for _, s, _ in host]
    ids = sorted(events["devices"])[:n_devices]
    kernel = other = 0
    coll = []
    n_k = n_o = n_c = 0
    busy_total = 0
    by_op = {}
    gaps = []
    for dev in ids:
        ivs = []
        dev_coll = 0
        for name, s, d in events["devices"][dev]:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            by_op[name] = by_op.get(name, 0) + (e - s)
            if KERNEL.search(name):
                kernel += e - s
                n_k += 1
            elif COLLECTIVE.search(name):
                dev_coll += e - s
                n_c += 1
            else:
                other += e - s
                n_o += 1
        coll.append(dev_coll)
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged)
        if dev == ids[0]:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, _label(host, starts, (a + b) // 2),
                                 a - w0))
    n = max(len(ids), 1)
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "n_devices": len(ids),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "kernel_ns": kernel / n, "kernel_events": n_k,
        "other_ns": other / n, "other_events": n_o,
        "collective_ns_max": max(coll) if coll else 0,
        "collective_events": n_c,
        "host_spans": spans,
        "top_ops": [[name, ns / n / 1e9] for name, ns in ops],
        "idle_gaps": [[label, ns / 1e9] for ns, label, _ in gaps[:top]],
        "gaps_at_s": [[at / 1e9, ns / 1e9] for ns, _, at in gaps[:3]],
    }


def _label(host, starts, t):
    """The benchmark's host span running at time ``t`` ('between spans'
    when none is); ``host`` is sorted by ``starts`` and its spans are
    sequential."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < host[i][1] + host[i][2]:
        return host[i][0]
    return "between spans"
