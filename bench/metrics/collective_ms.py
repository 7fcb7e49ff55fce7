"""Device time per sweep, in ms, of the collectives of the sharded sweep
(all-gather, all-reduce, collective-permute, reduce-scatter, all-to-all),
on the chip that spends most on them.  A cell on one chip has none, and
reads nothing."""


def read(ctx):
    s = ctx["summary"]
    if not s["collective_events"] or not ctx["sweeps"]:
        return None
    return s["collective_ns_max"] / ctx["sweeps"] / 1e6
