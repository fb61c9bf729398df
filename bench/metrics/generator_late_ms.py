"""95th percentile, by nearest rank, of how late the load generator sent
the requests due in the untraced part of the window (actual send time
minus scheduled arrival), so that a starved generator is not read as a
fast server."""
from pct import nearest_rank


def read(ctx):
    late = [
        (s.sent - s.due) * 1e3
        for s in ctx.due_in(ctx.t0, ctx.t_split)
        if s.sent is not None
    ]
    return nearest_rank(late, 95.0)
