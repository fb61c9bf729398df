"""95th percentile, by nearest rank, of the latency of every request due
in the window: from its scheduled arrival to the moment the benchmark saw
its answer. A request answered with an error (shed, deadline passed,
failed) or never answered counts as ``loadgen.GRACE_S`` late, the longest
the benchmark waits for any answer, so that a run in which more than one
request in twenty failed still reports, and reads far off."""
from loadgen import GRACE_S
from pct import nearest_rank


def read(ctx):
    lat = [
        (s.seen - s.due) * 1e3 if s.ok and s.seen is not None else GRACE_S * 1e3
        for s in ctx.due_in(ctx.t0, ctx.t_end)
    ]
    return nearest_rank(lat, 95.0)
