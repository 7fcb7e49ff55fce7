"""Device time per sweep, in ms, of the ops whose innermost stage scope is
``book``: the kernel path's bookkeeping
(core/engine._kernel_bookkeeping, vmapped over the streams). Ops that
are not the kernel or a collective, averaged over the chips of the cell;
read from the stage trace of ``bench/stagetrace.py``."""

from bench import stagetrace


def read(ctx):
    return stagetrace.stage_ms(stagetrace.summary(ctx), "book")
