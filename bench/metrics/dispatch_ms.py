"""Host time per sweep, in ms, from the call of run_trials to its return
(the enqueue): the mean of the benchmark's own ``dispatch`` spans in the
traced window."""


def read(ctx):
    spans = ctx["summary"]["host_spans"].get("dispatch")
    if not spans:
        return None
    return spans["total_ns"] / spans["n"] / 1e6
