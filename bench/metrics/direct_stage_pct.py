"""Share of the dispatched micro-batches, in percent, that the Engine
staged straight from one request's frames, with no host copy in front of
the stage: ``100 * n_direct / n_batches`` of its counters over the
untraced part of the window. It reads 100 where every request fills
whole micro-batches and 0 where every micro-batch gathers several
requests. None where the Engine keeps no such counter or dispatched
nothing."""


def read(ctx):
    st = ctx.host_stats
    direct = getattr(st, "n_direct", None)
    if direct is None or not st.n_batches:
        return None
    return 100.0 * direct / st.n_batches
