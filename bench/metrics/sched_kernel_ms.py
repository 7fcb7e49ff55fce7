"""Device time of the sched_select Pallas kernel per sweep, in ms: the
summed durations of the kernel's trace events, averaged over the chips of
the cell, divided by the sweeps of the traced window."""


def read(ctx):
    s = ctx["summary"]
    if not s["kernel_events"] or not ctx["sweeps"]:
        return None
    return s["kernel_ns"] / ctx["sweeps"] / 1e6
