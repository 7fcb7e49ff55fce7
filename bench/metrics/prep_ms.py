"""Device time per sweep, in ms, of the ops whose innermost stage scope is
``prep``: trial keys, workloads, initial loads and cluster traces
(core/simulate._prep_trials and run_trials' key split). Ops that are not
the kernel or a collective, averaged over the chips of the cell; read
from the stage trace of ``bench/stagetrace.py``."""

from bench import stagetrace


def read(ctx):
    return stagetrace.stage_ms(stagetrace.summary(ctx), "prep")
