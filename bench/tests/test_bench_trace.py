"""The trace reducer on a small synthetic trace, written as an XSpace
text proto so that every number below can be worked out by hand.

Device ops (us): kernel 10-30, fusion 25-40 (overlaps the kernel), kernel
60-70, copy 95-120. Program runs (``XLA Modules``): the mark at 0-2 and
100-101, the forward at 10-40 and 60-70, the output check at 95-120.
Window: 2-100 us. Busy is 10-40, 60-70 and 95-100: 45 us. Idle gaps:
2-10 (before the forward), 40-60 (before the forward), 70-95 (before the
check).
"""
import pytest
from jax.profiler import ProfileData

import registry
import tracereduce

US = 1_000_000  # ps per microsecond


def _ev(meta, start_us, dur_us):
    return (
        f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
        f"duration_ps: {dur_us * US} }}"
    )


def _meta(i, name):
    return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'


KERNEL = "%stream_conv_pyramid_pallas.1 = f32[256,4,4,64] custom-call(s8[256,60,36,3] %pad.2)"

TRACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_ev(1, 10, 20)} {_ev(2, 25, 15)} {_ev(1, 60, 10)} {_ev(3, 95, 25)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_ev(4, 0, 2)} {_ev(5, 10, 30)} {_ev(5, 60, 10)} {_ev(6, 95, 25)} {_ev(4, 100, 1)}
  }}
  {_meta(1, KERNEL)}
  {_meta(2, "%fusion.3 = s8[256,32,32,3] fusion(f32[256,32,32,3] %copy.5)")}
  {_meta(3, "%copy.5 = f32[256,32,32,3] copy(f32[256,32,32,3] %x)")}
  {_meta(4, "jit_" + tracereduce.MARK + "(77)")}
  {_meta(5, "jit__lambda(824830274744188033)")}
  {_meta(6, "jit_isfinite(833433148472569225)")}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {_ev(1, 0, 100)} }}
  {_meta(1, "host events are not read")}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    return tracereduce.from_profile(ProfileData.from_text_proto(TRACE))


def test_window_and_busy_union(reduced):
    assert reduced.window_s == pytest.approx(98e-6)
    assert reduced.busy_s() == pytest.approx(45e-6)


def test_per_op_sums_clip_to_the_window(reduced):
    ops = reduced.op_seconds()
    assert ops["stream_conv_pyramid_pallas.1"] == pytest.approx(30e-6)
    assert ops["fusion.3"] == pytest.approx(15e-6)
    assert ops["copy.5"] == pytest.approx(5e-6)


def test_kernel_events_are_whole_and_start_in_the_window(reduced):
    evs = reduced.events(r"^%stream_conv_pyramid_pallas\b")
    assert [(s, e) for s, e, _ in evs] == [(10_000, 30_000), (60_000, 70_000)]
    assert [(s, e) for s, e, _ in reduced.events(r"^%copy")] == [(95_000, 120_000)]


def test_longest_gaps_named_by_the_run_that_ends_them(reduced):
    gaps = reduced.idle_gaps(10)
    assert [g[1] for g in gaps] == pytest.approx([25e-6, 20e-6, 8e-6])
    assert [g[0] for g in gaps] == [
        "before jit_isfinite", "before jit__lambda", "before jit__lambda",
    ]
    assert len(reduced.idle_gaps(2)) == 2


def test_a_trace_without_two_marks_is_refused():
    bare = TRACE.replace(tracereduce.MARK, "something_else")
    with pytest.raises(ValueError, match="mark"):
        tracereduce.from_profile(ProfileData.from_text_proto(bare))


def test_metric_readers_on_the_synthetic_trace(reduced):
    bm = registry.benchmark()

    class Ctx:
        trace = reduced
        device_kind = "TPU v5 lite"
        cfg = registry.config(bm, "cifar10_int8")

    idle = registry.metric_reader("device_idle_pct.bulk").read(Ctx)
    assert idle == pytest.approx(100.0 * (1 - 45 / 98))
    busy = registry.metric_reader("pyramid_busy_pct").read(Ctx)
    assert busy == pytest.approx(100.0 * 30 / 45)
    roof = registry.metric_reader("pyramid_roofline_pct").read(Ctx)
    # Two calls of 256 frames, compute-bound at the int8 peak, in 30 us.
    want = 100.0 * (2 * 256 * 24_576_000 / 393e12) / 30e-6
    assert roof == pytest.approx(want)
