"""Device time per sweep, in ms, of the ops whose innermost stage scope is
``engine_prep``: the split of each stream into client slices and
windows, the step grouping (a sort), the seeds and per-window rates
(core/simulate._sched_trials, core/engine.run_stream_batch). Ops that
are not the kernel or a collective, averaged over the chips of the cell;
read from the stage trace of ``bench/stagetrace.py``."""

from bench import stagetrace


def read(ctx):
    return stagetrace.stage_ms(stagetrace.summary(ctx), "engine_prep")
