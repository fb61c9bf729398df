"""The Engine's host measurements as the benchmark reads them, on the
synthetic trace of ``test_bench_trace`` (device times in us there).

The host clock runs ``OFF`` ns ahead of the device's. The two window
marks ran on the device at 0-2 and 100-101 us; the host bracketed their
calls at -0.5 to 2.7 and 99.7 to 101.2 us (device time), so the offsets
both allow are ``OFF`` - 300 to ``OFF`` + 200 ns: the mapping takes the
middle, ``OFF`` - 50, and reports the width, 500 ns.

Engine spans (device us): wait (not full) 2-9; flush 9-45 holding pack
9-9.5, group 9.5-40.5 (stage 9.5-9.9, forward 9.9-40.2, check 40.2-40.5),
fetch 40.5-41 and complete 41-45; gc (generation 2) 46-58; flush 58.8-74
holding group 58.8-72 (stage 59-59.9, forward 59.9-70.2, check 70.2-72)
and complete 72-74; wait (empty) 75-94; requests queued 0-9 and 30-58.8.
The idle gaps 2-10, 40-60 and 70-95 (53 us) are covered 8 + 18 + 23 us by
spans other than ``queued``, 7.95 + 18.2 + 23.05 us once mapped.
"""
import time

import numpy as np
import pytest
from jax.profiler import ProfileData

import hostspans
import registry
import tracereduce
from test_bench_trace import TRACE

from repro.core.dhm import spans

OFF = 5_000_000
STAMPS = [(OFF - 500, OFF + 2_700), (OFF + 99_700, OFF + 101_200)]


def _t(us):
    """Device microseconds -> host perf_counter seconds."""
    return (us * 1e3 + OFF) / 1e9


def _log():
    log = spans.SpanLog(64)
    log.add(spans.WAIT, 0, -1, _t(2), _t(9), 1)
    f1 = log.begin(spans.FLUSH, 1, -1, _t(9))
    log.add(spans.QUEUED, 0, f1, _t(0), _t(9))
    log.add(spans.PACK, 0, f1, _t(9), _t(9.5))
    g1 = log.begin(spans.GROUP, 0, f1, _t(9.5))
    log.add(spans.STAGE, 0, g1, _t(9.5), _t(9.9))
    log.add(spans.FORWARD, 0, g1, _t(9.9), _t(40.2))
    log.add(spans.CHECK, 0, g1, _t(40.2), _t(40.5))
    log.end(g1, _t(40.5), 4)
    log.add(spans.FETCH, 0, f1, _t(40.5), _t(41))
    log.add(spans.COMPLETE, 0, f1, _t(41), _t(45))
    log.end(f1, _t(45), 1)
    log.add(spans.GC, 0, -1, _t(46), _t(58), 2)
    f2 = log.begin(spans.FLUSH, 2, -1, _t(58.8))
    log.add(spans.QUEUED, 1, f2, _t(30), _t(58.8))
    g2 = log.begin(spans.GROUP, 0, f2, _t(58.8))
    log.add(spans.STAGE, 0, g2, _t(59), _t(59.9))
    log.add(spans.FORWARD, 0, g2, _t(59.9), _t(70.2))
    log.add(spans.CHECK, 0, g2, _t(70.2), _t(72))
    log.end(g2, _t(72), 4)
    log.add(spans.COMPLETE, 0, f2, _t(72), _t(74))
    log.end(f2, _t(74), 1)
    log.add(spans.WAIT, 0, -1, _t(75), _t(94), 0)
    return log.close()


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_text_proto(TRACE)


@pytest.fixture(scope="module")
def ctx(pd):
    return _Ctx(
        trace=tracereduce.from_profile(pd), spans=_log(),
        mark_stamps=STAMPS, mark_runs=hostspans.mark_runs(pd),
    )


def test_idle_intervals_agree_with_idle_gaps(ctx):
    reduced = ctx.trace
    for top in (None, 10, 2):
        got = hostspans.idle_intervals(reduced, top)
        want = reduced.idle_gaps(10 if top is None else top)
        assert [[label, (e - s) / 1e9] for s, e, label in got] == want
    assert [(s, e) for s, e, _ in hostspans.idle_intervals(reduced)] == [
        (70_000, 95_000), (40_000, 60_000), (2_000, 10_000),
    ]


def test_clock_offset_from_the_mark_brackets(ctx):
    assert ctx.mark_runs == [(0, 2_000), (100_000, 101_000)]
    offset, width = hostspans.clock_offset(STAMPS, ctx.mark_runs)
    assert offset == OFF - 50 and width == 500
    late = [STAMPS[0], (OFF + 100_900, OFF + 102_000)]  # the clocks drifted
    with pytest.raises(ValueError, match="disagree"):
        hostspans.clock_offset(late, ctx.mark_runs)


def test_gaps_are_named_by_the_span_covering_most(ctx):
    mapped, _ = hostspans.mapped(ctx)
    got = hostspans.label_gaps(ctx.trace, mapped)
    old = ctx.trace.idle_gaps(10)
    assert [g[1] for g in got] == [g[1] for g in old]
    assert [g[0] for g in got] == [
        "wait (empty), before jit_isfinite",
        "gc (generation 2), before jit__lambda",
        "wait (not full), before jit__lambda",
    ]
    # A span that overlaps a gap as much as its parent names it, and a gap
    # across several phases is named by the phase that holds most of it.
    assert mapped.covering(10_000, 40_000) == "forward"
    assert mapped.covering(39_000, 46_000) == "complete"
    assert mapped.covering(200_000, 300_000) is None


def test_idle_attributed_and_forward_contained(ctx):
    mapped, _ = hostspans.mapped(ctx)
    attributed = registry.metric_reader("idle_attributed_pct.bulk").read(ctx)
    # 50 ns more of each gap is covered than the spans' device times say:
    # the mapping puts host time 50 ns late.
    assert attributed == pytest.approx(100.0 * 49.2 / 53, abs=1e-3)
    assert hostspans.forward_contained_pct(ctx.trace, mapped) == 100.0


def test_queue_wait_and_flush_phases(ctx):
    wait = registry.metric_reader("queue_wait_ms.cameras").read(ctx)
    assert wait == pytest.approx(0.0288, abs=1e-6)  # the longer of 9 and 28.8 us
    phases = {p: (ms, mx) for p, ms, mx in hostspans.flush_phases(ctx.spans)}
    assert set(phases) == {
        "pack", "stage", "forward", "check", "fetch", "complete",
        "group self", "flush self", "wait", "gc",
    }
    assert phases["forward"] == pytest.approx((0.0203, 0.0303), abs=1e-6)
    assert phases["group self"] == pytest.approx((0.0001, 0.0002), abs=1e-6)
    assert phases["gc"] == pytest.approx((0.006, 0.012), abs=1e-6)


class _Stats:
    """An ``EngineStats`` as the program before the phase counters had it."""

    n_batches, n_frames, busy_s = 10, 60, 0.05


def test_readers_return_none_where_their_input_is_absent(ctx):
    untraced = _Ctx(trace=None, host_stats=_Stats())
    for name in ("queue_wait_ms.cameras", "idle_attributed_pct.bulk",
                 "pack_ms.bulk", "stage_ms.bulk", "device_wait_ms.bulk",
                 "check_ms.bulk", "fetch_ms.bulk", "complete_ms.bulk",
                 "pad_frames_pct.cameras"):
        assert registry.metric_reader(name).read(untraced) is None, name
    no_spans = _Ctx(trace=ctx.trace, host_stats=_Stats())
    assert registry.metric_reader("idle_attributed_pct.bulk").read(no_spans) is None


def test_counter_readers_on_the_engines_stats():
    from repro.core.dhm import EngineStats

    st = EngineStats(
        n_requests=6, n_frames=6, n_batches=2, busy_s=0.010,
        mean_latency_s=0.0, max_latency_s=0.0, pack_s=0.001, stage_s=0.002,
        device_wait_s=0.004, check_s=0.0005, fetch_s=0.0002,
        complete_s=0.003, n_slots=16,
    )
    ctx = _Ctx(host_stats=st)
    read = {n: registry.metric_reader(n).read(ctx) for n in (
        "pack_ms.bulk", "stage_ms.bulk", "device_wait_ms.bulk",
        "check_ms.bulk", "fetch_ms.bulk", "complete_ms.bulk",
        "pad_frames_pct.cameras")}
    assert read == pytest.approx({
        "pack_ms.bulk": 0.5, "stage_ms.bulk": 1.0, "device_wait_ms.bulk": 2.0,
        "check_ms.bulk": 0.25, "fetch_ms.bulk": 0.1, "complete_ms.bulk": 1.5,
        "pad_frames_pct.cameras": 62.5,
    })


def _fake_profile_data(marks, log, off):
    """A profile whose device ran the window marks at ``marks`` and one
    forward program inside each ``forward`` span of ``log``, on a clock
    ``off`` ns behind the host's."""

    class Ev:
        def __init__(self, s, e, name):
            self.start_ns, self.end_ns, self.name = float(s), float(e), name

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    cols = log.columns()
    fwd = np.flatnonzero(cols["kind"] == spans.FORWARD)
    # In from each end by the widest mark: more than the mapping can be off.
    pad = max(e - s for s, e in marks)
    runs = [
        (cols["start_ns"][i] - off + pad, cols["end_ns"][i] - off - pad)
        for i in fwd if cols["end_ns"][i] - cols["start_ns"][i] > 3 * pad
    ]
    mark = f"jit_{tracereduce.MARK}(1)"
    ops = [Ev(s, e, "%fusion.1 = f32[16,10] fusion()") for s, e in runs]
    modules = [Ev(s, e, mark) for s, e in marks]
    modules += [Ev(s, e, "jit__lambda(1)") for s, e in runs]

    class Plane:
        name = "/device:TPU:0"
        lines = [Line(tracereduce.OPS_LINE, ops),
                 Line(tracereduce.MODULES_LINE, modules)]

    class PD:
        planes = [Plane]

    return PD


def test_spanrun_on_the_cpu_with_a_synthetic_device(monkeypatch):
    """The whole traced run on the CPU, with the profiler replaced by a
    device whose clock runs ``off`` behind the host's and whose program
    runs sit inside the forward spans: the mapping recovers the offset,
    every forward span contains its run, and the gaps carry span names."""
    import jax

    import run
    import spanrun
    from repro.core.dhm import Engine
    from test_bench_run import ARGS, SMALL_BULK

    off = time.perf_counter_ns() - 10**9
    marks, logs = [], []

    def fake_profile(jax_, out_dir, until, window_mark):
        for k in range(2):
            before = time.perf_counter_ns()
            window_mark()
            after = time.perf_counter_ns()
            q = (after - before) // 4
            marks.append((before + q - off, after - q - off))
            if not k:
                time.sleep(max(0.0, until - time.perf_counter()))
        return "synthetic.xplane.pb"

    stop = Engine.stop_spans

    def kept_stop(self):
        logs.append(stop(self))
        return logs[-1]

    class FakeProfileData:
        @staticmethod
        def from_file(path):
            return _fake_profile_data(marks, logs[-1], off)

    import costs

    v5e_peaks = costs.peaks("TPU v5 lite")  # the readers price a CPU run so
    monkeypatch.setattr(costs, "peaks", lambda kind: v5e_peaks)
    monkeypatch.setattr(run, "_jax", lambda: jax)
    monkeypatch.setattr(run, "_profile", fake_profile)
    monkeypatch.setattr(Engine, "stop_spans", kept_stop)
    monkeypatch.setattr(jax.profiler, "ProfileData", FakeProfileData)
    real_run = run.run
    monkeypatch.setattr(run, "run", lambda argv: real_run(
        argv, chip_checks=False, traffic_overrides=SMALL_BULK))

    out = spanrun.traced_with_spans(ARGS[:-2])
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    sp = out["spans"]
    assert sp["rows"] > 0 and sp["dropped"] == 0
    assert sp["forward_contained_pct"] == 100.0
    assert 0.0 < sp["idle_attributed_pct"] <= 100.0
    assert sp["queue_wait_ms"] is not None and sp["dispatch_ms_traced"] > 0
    assert 0 <= out["device"]["clock_bracket_us"] * 1e3 <= max(e - s for s, e in marks)
    assert out["breakdown"]["idle_gaps"]
    assert all(", " in g[0] for g in out["breakdown"]["idle_gaps"])
    assert "forward" in {p[0] for p in out["breakdown"]["flush_phases"]}
    assert {"pack_ms.bulk", "device_wait_ms.bulk"} <= set(out["metrics"])


@pytest.mark.parametrize("fake_run, says", [
    # An Engine built some other way: the profile finds none to arm.
    (lambda argv: __import__("run")._profile(None, None, 0.0, lambda: None),
     "built no Engine"),
    # A traced run that no longer profiles through ``run._profile``.
    (lambda argv: {}, "without calling run._profile"),
])
def test_spanrun_fails_clearly_where_run_changed(monkeypatch, fake_run, says):
    import run
    import spanrun

    monkeypatch.setattr(run, "run", fake_run)
    with pytest.raises(run.RunFailed, match=says):
        spanrun.traced_with_spans(["--workload", "cifar10_int8.bulk"])
