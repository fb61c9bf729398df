"""95th percentile, by nearest rank, of how long a request waited in the
Engine's queue, from ``submitted_at`` to the flush that took it: the
``queued`` spans of the Engine's span log, armed over the traced part of
the window. None where the run armed no span log."""
import hostspans


def read(ctx):
    log = getattr(ctx, "spans", None)
    return None if log is None else hostspans.queue_wait_ms(log)
