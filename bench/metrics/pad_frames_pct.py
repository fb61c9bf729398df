"""Share of the dispatched frame slots, in percent, that carried padding
and not a request's frame: ``100 * (n_slots - n_frames) / n_slots`` of
the Engine's counters over the untraced part of the window. A request
dispatched but not yet answered at the part's end counts its slots and
not its frames, so the share can read high by one flush's frames."""


def read(ctx):
    st = ctx.host_stats
    slots = getattr(st, "n_slots", None)
    if not slots:
        return None
    return 100.0 * (slots - st.n_frames) / slots
