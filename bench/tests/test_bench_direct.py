"""The reader of ``direct_stage_pct.bulk``, on the Engine's own stats and
on stats that lack its counter."""
import types

import pytest

import registry

from repro.core.dhm import EngineStats


def _engine_stats(n_batches, n_direct):
    return EngineStats(
        n_requests=4, n_frames=16, n_batches=n_batches, busy_s=0.01,
        mean_latency_s=0.0, max_latency_s=0.0, n_direct=n_direct,
    )


@pytest.mark.parametrize("stats, want", [
    (_engine_stats(16, 16), 100.0),  # every request fills its group
    (_engine_stats(4, 2), 50.0),  # requests split (5, 1, 7, 1) by 4
    (_engine_stats(4, 0), 0.0),  # every group gathers several requests
    (_engine_stats(0, 0), None),  # nothing dispatched
    (types.SimpleNamespace(n_batches=4), None),  # an Engine without it
])
def test_direct_stage_pct_reads_the_engines_counter(stats, want):
    ctx = types.SimpleNamespace(host_stats=stats)
    got = registry.metric_reader("direct_stage_pct.bulk").read(ctx)
    assert got == (None if want is None else pytest.approx(want))
