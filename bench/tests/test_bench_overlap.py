"""The reader of ``overlap_pct.bulk``, on the Engine's own stats and on
stats that lack its counter."""
import types

import pytest

import registry

from repro.core.dhm import EngineStats


def _engine_stats(n_batches, n_overlapped):
    return EngineStats(
        n_requests=4, n_frames=16, n_batches=n_batches, busy_s=0.01,
        mean_latency_s=0.0, max_latency_s=0.0, n_overlapped=n_overlapped,
    )


@pytest.mark.parametrize("stats, want", [
    (_engine_stats(16, 15), 93.75),  # one flush of 16 micro-batches
    (_engine_stats(4, 0), 0.0),  # four flushes of one
    (_engine_stats(0, 0), None),  # nothing dispatched
    (types.SimpleNamespace(n_batches=4), None),  # an Engine without it
])
def test_overlap_pct_reads_the_engines_counter(stats, want):
    ctx = types.SimpleNamespace(host_stats=stats)
    got = registry.metric_reader("overlap_pct.bulk").read(ctx)
    assert got == (None if want is None else pytest.approx(want))
