"""Tests for the serving engine (single-device fast tier): the request
queue / micro-batching, double-buffered donated closures, warmup, stats,
deadline SLOs, admission control, and the execution paths extracted from
the compiler (eager forward, cached jitted forward, pipeline_spec /
StageIOSpec emission). Fault injection lives in test_faults.py."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm import spans
from repro.core.dhm.compiler import QuantSpec, compile_dhm
from repro.core.dhm.engine import (
    DeadlineExceeded,
    Engine,
    FlusherWedged,
    Shed,
    forward,
    plan_jitted_forward,
)
from repro.core.dhm.faults import (
    DelayedFlush,
    FaultPlan,
    NaNActivation,
    StalledDispatch,
)
from repro.core.dhm.pipeline import StageIOSpec, derive_io_specs
from repro.models.cnn import ALL_TOPOLOGIES, LENET5, init_cnn


def _plan(name="lenet5", n_stages=1, **quant_kw):
    topo = ALL_TOPOLOGIES[name]
    params = init_cnn(jax.random.PRNGKey(0), topo)
    quant = QuantSpec(**quant_kw) if quant_kw else QuantSpec()
    return topo, compile_dhm(topo, params, quant=quant, n_stages=n_stages)


def _frames(topo, n, seed=1):
    h, w = topo.input_shape
    return jax.random.normal(
        jax.random.PRNGKey(seed), (n, h, w, topo.input_channels)
    )


class TestStageIO:
    def test_compiled_stages_carry_chaining_io(self):
        """The compiler emits a StageIOSpec per stage that chains
        edge-to-edge and ends at the topology's feature shape."""
        topo, plan = _plan("cifar10", n_stages=3)
        h, w = topo.input_shape
        assert plan.stages[0].io.in_shape == (h, w, topo.input_channels)
        for a, b in zip(plan.stages[:-1], plan.stages[1:]):
            assert a.io.out_shape == b.io.in_shape
        assert plan.stages[-1].io.out_shape == topo.feature_shape()

    def test_heterogeneous_stages_have_pipeline_spec(self):
        """Heterogeneous stages (different specs per stage) now emit a
        pipeline spec instead of refusing — the old homogeneity
        restriction is gone."""
        _, plan = _plan("lenet5", n_stages=2)
        fns, params, io = plan.pipeline_spec()
        assert len(fns) == len(params) == len(io) == 2
        assert io[0].out_shape == io[1].in_shape
        assert io[0].in_shape != io[1].in_shape  # genuinely heterogeneous

    def test_derive_io_specs_matches_compiler(self):
        """eval_shape chaining over the emitted stage bodies recovers the
        same geometry the compiler computed from the topology."""
        topo, plan = _plan("cifar10_full", n_stages=3)
        fns, params, io = plan.pipeline_spec()
        derived = derive_io_specs(fns, params, io[0].in_shape)
        assert tuple(derived) == tuple(io)

    def test_bad_io_spec_raises(self):
        with pytest.raises(ValueError, match="positive ints"):
            StageIOSpec(in_shape=(0, 4, 4), out_shape=(4, 4, 4))


class TestEngineQueue:
    def test_requests_match_plan(self):
        """Queued requests of uneven sizes are packed into micro-batches
        (zero-padded tail) and each gets exactly its own logits back."""
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        x = _frames(topo, 7)
        r1, r2, r3 = eng.submit(x[:3]), eng.submit(x[3:6]), eng.submit(x[6])
        eng.flush()
        got = jnp.concatenate([r1.result(), r2.result(), r3.result()])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        assert r3.result().shape == (1, topo.n_classes)  # single frame

    def test_result_triggers_flush(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        req = eng.submit(_frames(topo, 2))
        assert not req.done
        out = req.result()  # implicit flush
        assert req.done and out.shape == (2, topo.n_classes)
        assert req.latency_s > 0

    def test_no_retrace_across_flushes(self):
        """The donated closure is built once; repeated flushes reuse it
        (the jit cache holds exactly one entry)."""
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        for seed in range(3):
            eng.infer(_frames(topo, 4, seed=seed))
        assert plan_jitted_forward(plan, donate=True)._cache_size() == 1

    def test_quantized_plan_serves(self):
        topo, plan = _plan("lenet5", weight_bits=3, act_bits=3)
        eng = Engine(plan, microbatch=2)
        x = _frames(topo, 2)
        np.testing.assert_allclose(
            np.asarray(eng.infer(x)), np.asarray(plan(x)),
            rtol=1e-4, atol=1e-5,
        )

    def test_stats(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        eng.infer(_frames(topo, 6))
        st = eng.stats()
        assert st.n_requests == 1
        assert st.n_frames == 6
        assert st.n_batches == 2  # 6 frames -> two 4-frame µbatches
        assert st.frames_per_busy_s > 0
        assert st.max_latency_s >= st.mean_latency_s > 0
        assert "frames/busy-s" in st.summary()

    def test_flush_empty_queue_is_noop(self):
        _, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        eng.flush()
        assert eng.stats().n_frames == 0

    def test_bad_frame_shape_raises(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        with pytest.raises(ValueError, match="expected frames"):
            eng.submit(jnp.zeros((2, 14, 14, 1)))

    def test_bad_microbatch_raises(self):
        _, plan = _plan("lenet5")
        with pytest.raises(ValueError, match="microbatch"):
            Engine(plan, microbatch=0)

    def test_undonated_engine(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2, donate=False, warmup=False)
        x = _frames(topo, 2)
        out = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )


class TestDeadlines:
    def test_background_flusher_dispatches_for_deadline(self):
        """With a huge flush interval, only the request's deadline can
        trigger dispatch — the flusher must wake for it. The margin is
        wider than a loaded host's late wake (a thread waiting for the
        interpreter lock wakes milliseconds past its timeout), so the
        deadline has not passed at the take."""
        topo, plan = _plan("lenet5")
        with Engine(
            plan, microbatch=8, auto_flush=True, flush_interval_ms=5000.0,
            deadline_margin_ms=250.0,
        ) as eng:
            req = eng.submit(_frames(topo, 1), deadline_ms=1000.0)
            out = req.result(timeout=10.0)
        assert out.shape == (1, topo.n_classes)
        assert req.ok and req.latency_s < 2.0  # nowhere near the interval

    def test_background_flusher_dispatches_on_full_batch(self):
        topo, plan = _plan("lenet5")
        with Engine(
            plan, microbatch=4, auto_flush=True, flush_interval_ms=5000.0
        ) as eng:
            req = eng.submit(_frames(topo, 4))  # fills the micro-batch
            out = req.result(timeout=10.0)
        assert out.shape == (4, topo.n_classes)
        assert req.latency_s < 2.0

    def test_expired_deadline_is_a_structured_error(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        req = eng.submit(_frames(topo, 1), deadline_ms=0.001)
        time.sleep(0.01)
        with pytest.raises(DeadlineExceeded, match="deadline passed"):
            req.result()
        assert req.done and not req.ok
        assert eng.stats().n_deadline_exceeded == 1

    def test_default_deadline_applies(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2, default_deadline_ms=50.0)
        req = eng.submit(_frames(topo, 1))
        assert req.deadline_at is not None
        assert req.result().shape == (1, topo.n_classes)

    def test_every_request_completes_under_load(self):
        """Property: a random mix of sizes / deadlines through the
        background flusher with a bounded shedding queue — every request
        completes (never hangs), with logits or a structured error, and
        the terminal-outcome counters partition the request count."""
        topo, plan = _plan("lenet5")
        rng = np.random.default_rng(0)
        n_req = 30
        with Engine(
            plan, microbatch=4, auto_flush=True, flush_interval_ms=2.0,
            max_queue=8, admission="shed_oldest",
        ) as eng:
            reqs = []
            for i in range(n_req):
                n = int(rng.integers(1, 5))
                dl = (
                    float(rng.uniform(5.0, 50.0))
                    if rng.random() < 0.5 else None
                )
                reqs.append(eng.submit(_frames(topo, n, seed=i), deadline_ms=dl))
        # stop() drained the queue: nothing may still be pending.
        for r in reqs:
            assert r.done
            if r.ok:
                out = r.result()
                assert out.shape == (r.n_frames, topo.n_classes)
                assert bool(jnp.isfinite(out).all())
            else:
                assert isinstance(r.error, (DeadlineExceeded, Shed))
        st = eng.stats()
        assert st.n_failed == st.n_invalid == st.n_rejected == 0
        assert st.n_ok + st.n_shed + st.n_deadline_exceeded == n_req
        assert st.n_ok > 0


class TestAdmission:
    def test_block_policy_drains_inline(self):
        """Without a flusher, a blocked submitter drains the queue itself
        — submission never deadlocks and every request is served."""
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2, max_queue=1, admission="block")
        r1 = eng.submit(_frames(topo, 1))
        r2 = eng.submit(_frames(topo, 1, seed=2))  # forces an inline flush
        assert r1.done and r1.ok
        assert r2.result().shape == (1, topo.n_classes)
        assert eng.stats().n_ok == 2

    def test_admission_policy_validated(self):
        _, plan = _plan("lenet5")
        with pytest.raises(ValueError, match="admission policy"):
            Engine(plan, admission="drop_table")

    def test_hyphenated_policy_normalized(self):
        _, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2, max_queue=1, admission="shed-oldest")
        assert eng.admission == "shed_oldest"


class TestFlushSemantics:
    def test_double_flush_is_noop(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        eng.infer(_frames(topo, 2))
        n = eng.stats().n_batches
        eng.flush()
        eng.flush()
        assert eng.stats().n_batches == n

    def test_start_stop_idempotent(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        eng.start()
        eng.start()  # idempotent
        req = eng.submit(_frames(topo, 2))
        assert req.result(timeout=10.0).shape == (2, topo.n_classes)
        eng.stop()
        eng.stop()  # also idempotent
        # After stop, the engine still serves synchronously.
        assert eng.infer(_frames(topo, 2)).shape == (2, topo.n_classes)


class TestStatsWindowAndStop:
    """Satellites: per-rung latency percentiles, stats reset, the bounded
    flush quantum, and the loud wedged-stop path."""

    def test_per_rung_latency_percentiles(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        for i in range(8):
            eng.infer(_frames(topo, 4, seed=i))
        st = eng.stats()
        lat = st.rung_latency_ms["fused"]
        assert lat["n"] == 8
        assert 0 < lat["p50_ms"] <= lat["p99_ms"]
        assert "rung fused" in st.summary()

    def test_reset_stats_zeroes_window_but_keeps_ledger(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        eng.infer(_frames(topo, 4))
        assert eng.stats().n_ok == 1
        eng.reset_stats()
        st = eng.stats()
        assert st.n_requests == 0
        assert st.n_frames == 0
        assert st.n_ok == 0
        assert st.rung_latency_ms == {}
        # The engine still serves, and fresh completions repopulate.
        eng.infer(_frames(topo, 4, seed=2))
        st = eng.stats()
        assert st.n_ok == 1
        assert st.rung_latency_ms["fused"]["n"] == 1

    def test_flush_max_frames_is_one_quantum(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=2)
        reqs = [eng.submit(_frames(topo, 2, seed=i)) for i in range(3)]
        # One bounded flush takes whole requests up to ~max_frames from
        # the head — here exactly the first request.
        assert eng.flush(max_frames=2) == 2
        assert reqs[0].done
        assert not reqs[1].done and not reqs[2].done
        # The rest drains with an unbounded flush.
        assert eng.flush() == 4
        assert all(r.done for r in reqs)
        assert eng.flush() == 0

    def test_wedged_stop_raises_and_sheds(self):
        topo, plan = _plan("lenet5")
        eng = Engine(
            plan,
            microbatch=2,
            auto_flush=True,
            fault_plan=FaultPlan(
                [DelayedFlush(at=0, times=None, delay_s=2.0)], seed=0
            ),
        )
        # The flusher wakes for this and stalls 2 s inside the flush —
        # the stall hits before the queue pop, so both requests are
        # still queued when the bounded join gives up.
        first = eng.submit(_frames(topo, 2))
        time.sleep(0.3)
        second = eng.submit(_frames(topo, 2, seed=2))
        with pytest.raises(FlusherWedged, match="did not exit"):
            eng.stop(join_timeout_s=0.2)
        # Every queued request completed with a structured Shed — no
        # request left hanging, no silent thread leak.
        for req in (first, second):
            with pytest.raises(Shed):
                req.result(timeout=1.0)
        # The wedged flusher eventually wakes, finds nothing, and exits;
        # stop() is idempotent afterwards.
        eng.stop()


class TestEngineSpans:
    """The Engine's always-on phase counters and its span log, which
    records nothing unless armed."""

    INSIDE_BUSY = ("pack_s", "stage_s", "device_wait_s", "check_s", "fetch_s")

    @staticmethod
    def _by_kind(log, name):
        cols = log.columns()
        return np.flatnonzero(cols["kind"] == spans.KINDS.index(name))

    def test_phase_counters_lie_inside_busy_and_reset(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        for seed in range(3):
            eng.infer(_frames(topo, 6, seed=seed))
        st = eng.stats()
        phases = [getattr(st, f) for f in self.INSIDE_BUSY]
        assert all(p >= 0 for p in phases) and st.complete_s >= 0
        assert st.device_wait_s > 0
        assert sum(phases) <= st.busy_s
        eng.reset_stats()
        st = eng.stats()
        assert [getattr(st, f) for f in self.INSIDE_BUSY] == [0.0] * 5
        assert st.complete_s == 0.0 and st.n_slots == 0

    def test_slots_count_the_padding_of_a_flush(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        eng.submit(_frames(topo, 3))
        eng.submit(_frames(topo, 2, seed=2))
        eng.flush()  # 5 frames -> two 4-frame groups, 3 frames of padding
        st = eng.stats()
        assert st.n_frames == 5 and st.n_batches == 2
        assert st.n_slots - st.n_frames == 3

    def test_spans_nest_under_their_group_and_flush(self):
        topo, plan = _plan("lenet5")
        # The watchdog thread runs stage and forward: a timeout is set.
        eng = Engine(plan, microbatch=4, dispatch_timeout_s=60.0)
        log = eng.start_spans(4096)
        reqs = [eng.submit(_frames(topo, 3, seed=i)) for i in range(3)]
        eng.flush()
        eng.infer(_frames(topo, 4, seed=9))
        assert eng.stop_spans() is log and log.dropped == 0
        cols = log.columns()
        kind, parent = cols["kind"], cols["parent"]
        flush, group = spans.KINDS.index("flush"), spans.KINDS.index("group")
        children = [self._by_kind(log, k) for k in ("stage", "forward", "check")]
        assert all(len(rows) == 4 for rows in children)  # 3 + 1 groups
        for rows in children:
            assert (kind[parent[rows]] == group).all()
            assert (kind[parent[parent[rows]]] == flush).all()
        groups = self._by_kind(log, "group")
        assert sorted(cols["arg"][groups]) == [1, 4, 4, 4]  # real frames
        assert (cols["end_ns"] >= cols["start_ns"]).all()
        # One queued span per request, id = its index, under its flush.
        queued = self._by_kind(log, "queued")
        assert sorted(cols["id"][queued]) == [r.index for r in reqs] + [3]
        assert (kind[parent[queued]] == flush).all()
        fl = self._by_kind(log, "flush")
        assert list(cols["id"][fl]) == [1, 2] and list(cols["arg"][fl]) == [3, 1]

    def test_flush_loop_waits_and_collections_are_spans(self):
        import gc

        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4, flush_interval_ms=2.0)
        log = eng.start_spans(4096)
        with eng:
            eng.submit(_frames(topo, 2)).result(timeout=30)
            time.sleep(0.05)
            gc.collect()
        eng.stop_spans()
        assert len(self._by_kind(log, "wait")) > 0
        collected = self._by_kind(log, "gc")
        assert 2 in log.columns()["arg"][collected]

    def test_past_capacity_spans_are_dropped(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        log = eng.start_spans(3)
        for seed in range(3):
            eng.infer(_frames(topo, 4, seed=seed))
        eng.stop_spans()
        assert log.n == 3 and log.dropped > 0
        assert (log.kind >= 0).all()

    def test_stop_removes_the_gc_callback(self):
        import gc

        _, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        before = len(gc.callbacks)
        eng.start_spans(16)
        assert len(gc.callbacks) == before + 1
        eng.stop_spans()
        assert len(gc.callbacks) == before
        with pytest.raises(RuntimeError):
            eng.stop_spans()

    def test_nothing_is_recorded_while_unarmed(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        log = eng.start_spans(1024)
        eng.infer(_frames(topo, 4))
        eng.stop_spans()
        n = log.n
        assert n > 0
        eng.infer(_frames(topo, 4, seed=2))
        with eng:
            eng.submit(_frames(topo, 2, seed=3)).result(timeout=30)
        assert (log.kind[n:] == -1).all()
        assert eng._spans is None

    def test_recording_tracks_no_new_objects(self):
        import gc

        log = spans.SpanLog(20_000)
        gc.collect()
        before = len(gc.get_objects())
        t = time.perf_counter()
        for i in range(10_000):
            log.add(spans.PACK, i, -1, t, t + 1e-6)
        after = len(gc.get_objects())
        log.close()
        assert log.n == 10_000
        assert after - before < 100


class TestPipelinedFlush:
    """A flush of several micro-batches launches group k+1 before it
    finishes group k, and checks the logits on their host copy."""

    @staticmethod
    def _split(x, sizes):
        edges = np.cumsum((0,) + sizes)
        return [x[a:b] for a, b in zip(edges[:-1], edges[1:])]

    def test_multi_group_flush_is_bit_equal_to_groups_run_alone(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        x = np.asarray(_frames(topo, 14))
        reqs = [eng.submit(p) for p in self._split(x, (5, 1, 7, 1))]
        eng.flush()  # 14 frames -> four groups in one flush
        assert eng.stats().n_batches == 4
        got = np.concatenate([r.result() for r in reqs])
        alone = np.concatenate(
            [np.asarray(eng.infer(x[s : s + 4])) for s in range(0, 14, 4)]
        )
        np.testing.assert_array_equal(got, alone)

    @pytest.mark.parametrize("n_groups", [1, 3])
    def test_overlapped_counts_groups_launched_behind_another(self, n_groups):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4)
        eng.infer(_frames(topo, 4 * n_groups))
        st = eng.stats()
        assert st.n_batches == n_groups
        assert st.n_overlapped == n_groups - 1
        eng.reset_stats()
        assert eng.stats().n_overlapped == 0

    def test_next_group_is_staged_before_this_one_is_fetched(self):
        topo, plan = _plan("lenet5")
        eng = Engine(plan, microbatch=4, dispatch_timeout_s=60.0)
        log = eng.start_spans(4096)
        eng.infer(_frames(topo, 12))  # three groups in one flush
        eng.stop_spans()
        cols = log.columns()
        kind, parent = cols["kind"], cols["parent"]

        def by_group(name, col):
            rows = np.flatnonzero(kind == spans.KINDS.index(name))
            return {int(cols["id"][parent[r]]): cols[col][r] for r in rows}

        stage_start = by_group("stage", "start_ns")
        fetch_end = by_group("fetch", "end_ns")
        assert sorted(stage_start) == sorted(fetch_end) == [0, 1, 2]
        for k in (0, 1):
            assert stage_start[k + 1] < fetch_end[k]

    def test_nan_in_the_middle_group_retries_it_alone(self):
        topo, plan = _plan("lenet5")
        faults = FaultPlan([NaNActivation(at=1, times=1, stage=0)])
        eng = Engine(
            plan, microbatch=4, retry_backoff_s=1e-4, fault_plan=faults
        )
        x = np.asarray(_frames(topo, 12))
        reqs = [eng.submit(p) for p in self._split(x, (3, 5, 4))]
        eng.flush()  # three groups; the second one's logits are NaN
        assert all(r.done and r.ok for r in reqs)
        clean = Engine(plan, microbatch=4)
        want = [np.asarray(clean.infer(p)) for p in self._split(x, (3, 5, 4))]
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.result()), w)
        st = eng.stats()
        assert st.n_ok == 3 and st.n_frames == 12
        assert st.n_retries == 1 and st.n_demotions == 0
        # Launches 0, 1 and 2, the rerun of group 1, then group 2 again:
        # the dropped launch of group 2 drew an event of its own.
        assert faults.n_dispatch_events == 5

    def test_stall_in_a_pipelined_group_times_out_and_demotes(self):
        topo, plan = _plan("lenet5")
        eng = Engine(
            plan,
            microbatch=4,
            dispatch_timeout_s=0.2,
            retry_backoff_s=1e-4,
            fault_plan=FaultPlan(
                [StalledDispatch(at=1, times=1, stall_s=5.0, rung="fused")]
            ),
        )
        x = _frames(topo, 12)
        got = eng.infer(x)  # returns promptly: watchdog + demotion
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        st = eng.stats()
        assert st.n_demotions == 1 and eng.rung == "per_layer"
        assert st.n_retries == 0
        assert "did not complete" in eng.demotions[0]["reason"]


class TestExtractedExecution:
    def test_forward_is_cnn_apply_path(self):
        """engine.forward == the eager stage/head composition cnn_apply
        routes through (bitwise — same closures, same order)."""
        topo, plan = _plan("lenet5")
        x = _frames(topo, 2)
        np.testing.assert_array_equal(
            np.asarray(forward(plan, x)),
            np.asarray(plan.head_fn(plan.features(x))),
        )

    def test_jitted_forward_cached_per_plan(self):
        _, plan = _plan("lenet5")
        assert plan.jitted_forward() is plan.jitted_forward()
        assert plan.jitted_forward(donate=True) is not plan.jitted_forward()


class TestPackedPow2Stacked:
    """Satellite: the stacked-weight pow2 packing that used to live inline
    in examples/serve.py is now models.layers.pack_linear_pow2 (odd widths
    zero-padded, per-layer scales via vmap)."""

    def test_stacked_pack_matches_per_layer(self):
        from repro.core.quant.pow2 import project_pow2
        from repro.models.layers import linear, pack_linear_pow2

        k1, k2 = jax.random.split(jax.random.PRNGKey(5))
        w = jax.random.normal(k1, (3, 10, 7))  # stacked, odd width
        x = jax.random.normal(k2, (3, 4, 10))
        packed = pack_linear_pow2({"w": w, "b": jnp.ones((7,))})
        assert packed["codes"].shape == (3, 10, 4)  # ceil(8/2) per layer
        assert packed["scale"].shape == (3, 1, 7)
        for layer in range(3):
            got = linear(
                x[layer],
                {
                    "codes": packed["codes"][layer],
                    "scale": packed["scale"][layer],
                    "b": packed["b"],
                },
            )
            ref = (
                x[layer] @ project_pow2(w[layer], channel_axis=1)
                + jnp.ones((7,))
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
            )

    def test_pack_params_pow2_walks_trees(self):
        from repro.models.layers import pack_params_pow2

        params = {
            "stack": [{"w": jnp.ones((4, 6)), "b": jnp.zeros((6,))}],
            "norm": {"scale": jnp.ones((4,))},
        }
        out = pack_params_pow2(params)
        assert "codes" in out["stack"][0] and "w" not in out["stack"][0]
        assert out["norm"]["scale"].shape == (4,)  # non-linears untouched
