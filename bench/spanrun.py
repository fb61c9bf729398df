"""Run one benchmark cell traced, as ``run.py --trace 1`` does, with the
Engine's span log armed over the traced part, and print the result line
with what the spans add.

    python bench/spanrun.py --workload <cell> --seed <n> --seconds <s>

The run is ``run.run`` unchanged, except that the Engine's
``start_spans`` is called just before the profiler starts and
``stop_spans`` just after it stops, and each of the two window marks'
calls is bracketed with ``time.perf_counter_ns()``. The untraced part,
from which the counter and host-clock metrics come, runs as in
``run.py``. To the line it adds:

- ``device.clock_bracket_us``: the width of the host-minus-device clock
  offsets both window marks allow (``hostspans``);
- ``breakdown.idle_gaps`` with the covering Engine span in front of each
  label, and ``breakdown.flush_phases``;
- ``spans``: the readings of ``metrics/queue_wait_ms.py`` and
  ``metrics/idle_attributed_pct.py``, the share of ``forward`` spans that
  contain a device program run, the log's rows and drops, and
  ``dispatch_ms`` over the traced part (spans and profiler on) beside
  the untraced part's.

This is scaffolding: it swaps ``repro.core.dhm.Engine`` and
``run._profile`` for the run, so it works only while ``run.run`` looks
the Engine up in the package at call time and ``_profile`` keeps its
signature. Once ``run.py`` arms the spans itself under ``--trace 1`` and
``tracereduce`` has ``Reduced.idle_intervals``, this module and
``hostspans.idle_intervals`` go.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

import hostspans
import registry
import run
import tracereduce

CAPACITY = 1 << 18  # span rows: about 12 MiB, several times a 3 s trace


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _dispatch_ms(a, b):
    n = b.n_batches - a.n_batches
    return (b.busy_s - a.busy_s) / n * 1e3 if n else None


def traced_with_spans(argv) -> dict:
    """``run.run`` with ``--trace 1`` and the span log armed over the
    profile; returns the result line's object with the additions."""
    import repro.core.dhm as dhm
    from jax.profiler import ProfileData

    made, got = [], {}

    class Recorded(dhm.Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    profile = run._profile

    def armed_profile(jax, out_dir, until, window_mark):
        if not made:
            raise run.RunFailed(
                "run.run built no Engine through repro.core.dhm.Engine, so "
                "spanrun cannot arm its span log"
            )
        engine = made[-1]
        stamps = []

        def bracketed():
            before = time.perf_counter_ns()
            window_mark()
            stamps.append((before, time.perf_counter_ns()))

        got["before"] = engine.stats()
        engine.start_spans(CAPACITY)
        try:
            path = profile(jax, out_dir, until, bracketed)
        finally:
            got["spans"] = engine.stop_spans()
            got["after"] = engine.stats()
        got.update(path=path, stamps=stamps)
        return path

    engine_cls = dhm.Engine
    dhm.Engine, run._profile = Recorded, armed_profile
    try:
        out = run.run(list(argv) + ["--trace", "1"])
    finally:
        dhm.Engine, run._profile = engine_cls, profile
    if "spans" not in got:
        raise run.RunFailed(
            "run.run traced without calling run._profile, so spanrun armed "
            "no span log"
        )

    log = got["spans"]
    ctx = _Ctx(
        trace=tracereduce.read(got["path"]), spans=log,
        mark_stamps=got["stamps"],
        mark_runs=hostspans.mark_runs(ProfileData.from_file(got["path"])),
    )
    mapped, width = hostspans.mapped(ctx)
    out["device"]["clock_bracket_us"] = width / 1e3
    out["breakdown"]["idle_gaps"] = hostspans.label_gaps(ctx.trace, mapped)
    out["breakdown"]["flush_phases"] = hostspans.flush_phases(log)
    out["spans"] = {
        "queue_wait_ms": registry.metric_reader("queue_wait_ms").read(ctx),
        "idle_attributed_pct": registry.metric_reader("idle_attributed_pct").read(ctx),
        "forward_contained_pct": hostspans.forward_contained_pct(ctx.trace, mapped),
        "rows": log.n,
        "dropped": log.dropped,
        "dispatch_ms_traced": _dispatch_ms(got["before"], got["after"]),
        "dispatch_ms_untraced": got["before"].busy_s / got["before"].n_batches * 1e3,
    }
    # Move "checks" back to the end of the line, where run.py puts it.
    out["checks"] = out.pop("checks")
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    try:
        out = traced_with_spans(argv)
    except run.RunFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for c in out["checks"].values():
        if not np.isfinite(c["value"]):
            c["value"] = str(c["value"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(registry.ROOT / "src"))
    sys.exit(main())
