"""Share of the dispatched micro-batches, in percent, that the Engine
launched while an earlier micro-batch of the same flush was unfinished:
``100 * n_overlapped / n_batches`` of its counters over the untraced part
of the window. Over flushes of G micro-batches it reads 100 (G-1)/G, so
it also says how many micro-batches a flush carries. None where the
Engine keeps no such counter or dispatched nothing."""


def read(ctx):
    st = ctx.host_stats
    overlapped = getattr(st, "n_overlapped", None)
    if overlapped is None or not st.n_batches:
        return None
    return 100.0 * overlapped / st.n_batches
