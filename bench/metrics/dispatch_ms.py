"""Host milliseconds per dispatched micro-batch, from the Engine's own
counters over the untraced part of the window: ``busy_s / n_batches``
(time inside ``flush`` per jitted-forward call)."""


def read(ctx):
    st = ctx.host_stats
    return st.busy_s / st.n_batches * 1e3 if st.n_batches else None
