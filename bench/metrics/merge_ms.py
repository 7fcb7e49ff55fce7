"""Device time per sweep, in ms, of the ops whose innermost stage scope is
``merge``: the per_client cross-client merge
(kernels/sched_select/ops.sched_stream_grid, the fold in
core/simulate._sched_trials and, on a mesh, parallel/sweep.py with its
collectives left out); a shared_log cell has none and reads nothing. Ops
that are not the kernel or a collective, averaged over the chips of the
cell; read from the stage trace of ``bench/stagetrace.py``."""

from bench import stagetrace


def read(ctx):
    return stagetrace.stage_ms(stagetrace.summary(ctx), "merge")
