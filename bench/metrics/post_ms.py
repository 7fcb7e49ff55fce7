"""Device time per sweep, in ms, of the ops whose innermost stage scope is
``post``: the TrialResult stack (core/simulate._post_trials). Ops that
are not the kernel or a collective, averaged over the chips of the cell;
read from the stage trace of ``bench/stagetrace.py``."""

from bench import stagetrace


def read(ctx):
    return stagetrace.stage_ms(stagetrace.summary(ctx), "post")
