"""Device time per sweep, in ms, of every device op of the sweep program
other than the sched_select kernel and the collectives: the XLA stages
around the kernel (prep and post in core/simulate, the engine's window
split and step grouping, the per_client cross-client merge).  Averaged
over the chips of the cell."""


def read(ctx):
    s = ctx["summary"]
    if not s["other_events"] or not ctx["sweeps"]:
        return None
    return s["other_ns"] / ctx["sweeps"] / 1e6
