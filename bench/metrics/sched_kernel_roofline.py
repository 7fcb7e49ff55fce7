"""Share of the sched_select kernel's roofline, in %.

The kernel is bounded by bytes: the least time it could take is the
bytes it must read and write, over the chip's HBM bandwidth
(``peaks.json``).  That least time, per chip, over the kernel's measured
device time per chip (``sched_kernel_ms``) is the share.  The bytes come
from the LOGICAL shapes of the sweep, not from the kernel's padded
layout, so the number reads the same work whatever implements it.  The
kernel is a serial decision loop, so the share is far below 1%: it says
how far the loop is from moving its data at bandwidth, not that the data
is the limit."""

from bench.metrics import sched_kernel_ms


def logical_bytes(sh) -> int:
    """Bytes the kernel must move for one sweep of ``sh``
    (a ``reference.Shape``).

    Per stream (a trial under shared_log, a (trial, client) pair under
    per_client), with ``N`` request slots (its windows times the window):

    * reads: object ids (int32), step lengths (f32) and valid flags
      (int32) of every slot; the packed log, 4 rows of M servers (f32);
      the LCG seed (uint32);
    * writes: the chosen server (int32) and latency (f32) of every slot;
      the final packed log (4 x M f32); the post-drain load snapshot of
      every window (W x M f32); the 5 fused stream metrics (f32);

    and per trial, read once and shared by its clients: the true service
    rates at every window open (W x M f32)."""
    clients, per, window = sh.streams
    n_win = -(-per // window)
    n = n_win * window
    m = sh.n_servers
    streams = sh.n_trials * clients
    per_stream = (n * (4 + 4 + 4)          # object ids, lengths, valid
                  + 4 * m * 4              # log in
                  + 4                      # seed
                  + n * (4 + 4)            # choices, latencies
                  + 4 * m * 4              # log out
                  + n_win * m * 4          # window snapshots
                  + 5 * 4)                 # stream metrics
    per_trial = n_win * m * 4              # window rates
    return streams * per_stream + sh.n_trials * per_trial


def read(ctx):
    kernel_ms = sched_kernel_ms.read(ctx)
    if not kernel_ms:
        return None
    per_chip = logical_bytes(ctx["shape"]) / ctx["summary"]["n_devices"]
    least_s = per_chip / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ms / 1e3)
