"""Chaos suite: the serving engine under deterministic fault injection.

Contract asserted for EVERY injected fault class: the engine returns
structured per-request errors or demotes one rung of the execution ladder
and keeps serving — no hang, no crash — and surviving requests' logits
stay bit-exact vs the single-device plan. Also covers the demotion-ladder
order, retry-with-backoff healing, poisoned-batch isolation, the plan
self-check, and the dispatch watchdog."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.compiler import (
    PlanCheckError,
    QuantSpec,
    check_plan,
    compile_dhm,
)
from repro.core.dhm.engine import (
    BatchFailed,
    DeadlineExceeded,
    Engine,
    InvalidRequest,
    Rejected,
    Shed,
)
from repro.core.dhm.faults import (
    DelayedFlush,
    DeviceLoss,
    DispatchError,
    FaultPlan,
    NaNActivation,
    StalledDispatch,
)
from repro.core.dhm.pipeline import (
    CollectiveTimeout,
    call_with_timeout,
    make_pipeline_mesh,
)
from repro.models.cnn import ALL_TOPOLOGIES, init_cnn

TOPO = ALL_TOPOLOGIES["lenet5"]


@pytest.fixture(scope="module")
def plan():
    params = init_cnn(jax.random.PRNGKey(0), TOPO)
    return compile_dhm(TOPO, params, quant=QuantSpec())


def _frames(n, seed=1):
    h, w = TOPO.input_shape
    return jax.random.normal(
        jax.random.PRNGKey(seed), (n, h, w, TOPO.input_channels)
    )


def _engine(plan, **kw):
    kw.setdefault("microbatch", 4)
    kw.setdefault("retry_backoff_s", 1e-4)
    return Engine(plan, **kw)


# ---------------------------------------------------------------------------
# The fault plan itself: deterministic triggers.


class TestFaultPlan:
    def test_trigger_window(self):
        fp = FaultPlan([DispatchError(at=1, times=2)])
        effs = [fp.dispatch_effects(rung="fused") for _ in range(5)]
        assert [e.exc is not None for e in effs] == [
            False, True, True, False, False
        ]

    def test_forever_window(self):
        fp = FaultPlan([DispatchError(at=0, times=None)])
        assert all(
            fp.dispatch_effects(rung="x").exc is not None for _ in range(4)
        )

    def test_rung_filter(self):
        fp = FaultPlan([DeviceLoss(at=0, times=None, rung="mesh")])
        assert fp.dispatch_effects(rung="mesh").exc is not None
        assert fp.dispatch_effects(rung="fused").clean

    def test_seeded_probability_is_deterministic(self):
        def run():
            fp = FaultPlan([DispatchError(prob=0.5)], seed=7)
            return [
                fp.dispatch_effects(rung=None).exc is not None
                for _ in range(32)
            ]

        fires = [run(), run()]
        assert fires[0] == fires[1]
        assert any(fires[0]) and not all(fires[0])

    def test_flush_delay_counter(self):
        fp = FaultPlan([DelayedFlush(at=1, delay_s=0.25)])
        assert fp.on_flush() == 0.0
        assert fp.on_flush() == 0.25
        assert fp.on_flush() == 0.0

    def test_non_fault_spec_rejected(self):
        with pytest.raises(TypeError, match="Fault specs"):
            FaultPlan(["boom"])

    def test_tenant_scoped_window_is_deterministic_under_interleaving(self):
        """Each tenant advances its OWN event counter, so a tenant-scoped
        window fires at the same point in that tenant's stream no matter
        how other tenants' dispatches interleave."""
        fp = FaultPlan([DispatchError(at=1, times=1, tenant="A")])
        fired = []
        for _ in range(3):  # A/B/untenanted round-robin
            fired.append(fp.dispatch_effects(rung="fused", tenant="A"))
            assert fp.dispatch_effects(rung="fused", tenant="B").clean
            assert fp.dispatch_effects(rung="fused", tenant=None).clean
        # Only A's SECOND event is faulted — B and the untenanted stream
        # never see it even though they pass through the same plan.
        assert [e.exc is not None for e in fired] == [False, True, False]
        assert fp.n_dispatch_events_for("A") == 3
        assert fp.n_dispatch_events_for("B") == 3
        assert fp.n_dispatch_events_for(None) == 3


class TestWatchdog:
    def test_timeout_raises_instead_of_hanging(self):
        import time

        with pytest.raises(CollectiveTimeout, match="did not complete"):
            call_with_timeout(
                lambda: time.sleep(5), timeout_s=0.05, what="test sleep"
            )

    def test_value_and_error_pass_through(self):
        assert call_with_timeout(lambda: 42, timeout_s=1.0) == 42
        with pytest.raises(KeyError):
            call_with_timeout(
                lambda: (_ for _ in ()).throw(KeyError("k")), timeout_s=1.0
            )


# ---------------------------------------------------------------------------
# Plan self-check (the health probe).


class TestPlanCheck:
    def test_healthy_plan_passes(self, plan):
        check_plan(plan)
        plan.self_check()

    def test_nonfinite_params_fail(self, plan):
        bad_conv = list(plan.conv_params)
        bad_conv[0] = {
            "w": bad_conv[0]["w"].at[0, 0, 0, 0].set(jnp.nan),
            "b": bad_conv[0]["b"],
        }
        bad = dataclasses.replace(plan, conv_params=tuple(bad_conv))
        with pytest.raises(PlanCheckError, match="non-finite"):
            check_plan(bad)

    def test_inconsistent_io_fails(self, plan):
        st0 = plan.stages[0]
        bad_io = dataclasses.replace(
            st0.io, out_shape=(1, 1, st0.io.out_shape[-1])
        )
        bad = dataclasses.replace(
            plan,
            stages=(dataclasses.replace(st0, io=bad_io),) + plan.stages[1:],
        )
        with pytest.raises(PlanCheckError):
            check_plan(bad)

    def test_engine_refuses_unhealthy_plan(self, plan):
        bad_conv = list(plan.conv_params)
        bad_conv[0] = {
            "w": jnp.full_like(bad_conv[0]["w"], jnp.inf),
            "b": bad_conv[0]["b"],
        }
        bad = dataclasses.replace(plan, conv_params=tuple(bad_conv))
        with pytest.raises(PlanCheckError):
            _engine(bad)


# ---------------------------------------------------------------------------
# Fault classes, one by one: structured errors or one-rung demotion, and
# bit-exact survivors.


class TestTransientDispatchError:
    def test_retry_heals_bit_exact(self, plan):
        eng = _engine(
            plan, fault_plan=FaultPlan([DispatchError(at=0, times=1)])
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plan(x)))
        st = eng.stats()
        assert st.n_retries == 1
        assert st.n_demotions == 0
        assert eng.rung == "fused"

    def test_persistent_error_demotes_and_serves(self, plan):
        # 1 attempt + 2 retries all fail on the fused rung -> demote; the
        # per-layer rung serves the same batch (retry counter reset).
        eng = _engine(
            plan,
            fault_plan=FaultPlan([DispatchError(at=0, times=3, rung="fused")]),
            max_retries=2,
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        st = eng.stats()
        assert st.n_retries == 2
        assert st.n_demotions == 1
        assert eng.rung == "per_layer"
        assert eng.demotions[0]["rung"] == "fused"


class TestLadder:
    def test_demotion_order_and_exhaustion(self, plan):
        # Every rung's dispatch fails (no retries): the ladder walks
        # fused -> per_layer -> ref in order, the batch fails with a
        # structured error, and the engine KEEPS SERVING once the fault
        # clears (still on the last rung).
        eng = _engine(
            plan,
            fault_plan=FaultPlan([DispatchError(at=0, times=3)]),
            max_retries=0,
        )
        req = eng.submit(_frames(4))
        eng.flush()
        with pytest.raises(BatchFailed, match="batch failed"):
            req.result()
        assert [d["rung"] for d in eng.demotions] == [
            "fused", "per_layer", "ref"
        ]
        assert eng.rung == "ref"
        # Fault window closed: the engine still serves, on the last rung.
        x = _frames(4, seed=2)
        np.testing.assert_allclose(
            np.asarray(eng.infer(x)), np.asarray(plan(x)),
            rtol=1e-4, atol=1e-5,
        )
        st = eng.stats()
        assert st.n_failed == 1 and st.n_ok == 1
        assert "demotions" in st.summary()

    def test_allow_degraded_false_pins_the_rung(self, plan):
        eng = _engine(
            plan,
            fault_plan=FaultPlan([DispatchError(at=0, times=None)]),
            max_retries=0,
            allow_degraded=False,
        )
        req = eng.submit(_frames(4))
        eng.flush()  # must not raise: the failure is per-request
        with pytest.raises(BatchFailed):
            req.result()
        assert eng.rung == "fused"


class TestStalledDispatch:
    def test_timeout_demotes_instead_of_hanging(self, plan):
        eng = _engine(
            plan,
            fault_plan=FaultPlan(
                [StalledDispatch(at=0, times=1, stall_s=5.0, rung="fused")]
            ),
            dispatch_timeout_s=0.2,
        )
        x = _frames(4)
        got = eng.infer(x)  # returns promptly: watchdog + demotion
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        st = eng.stats()
        assert st.n_demotions == 1
        assert st.n_retries == 0  # timeouts demote, they don't retry
        assert "did not complete" in eng.demotions[0]["reason"]


class TestNaNActivation:
    def test_transient_corruption_retries_bit_exact(self, plan):
        eng = _engine(
            plan,
            fault_plan=FaultPlan([NaNActivation(at=0, times=1, stage=0)]),
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plan(x)))
        st = eng.stats()
        assert st.n_retries == 1 and st.n_demotions == 0

    def test_persistent_corruption_demotes(self, plan):
        # The fused rung keeps producing NaN logits -> retries burn ->
        # demote to per_layer, where the fault (rung-filtered) is gone.
        eng = _engine(
            plan,
            fault_plan=FaultPlan(
                [NaNActivation(at=0, times=None, stage=0, rung="fused")]
            ),
            max_retries=1,
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        st = eng.stats()
        assert st.n_demotions == 1 and eng.rung == "per_layer"
        assert "non-finite" in eng.demotions[0]["reason"]


class TestDeviceLoss:
    def test_device_loss_demotes_without_retry(self, plan):
        eng = _engine(
            plan, fault_plan=FaultPlan([DeviceLoss(at=0, times=1)])
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        st = eng.stats()
        assert st.n_demotions == 1
        assert st.n_retries == 0
        assert "device loss" in eng.demotions[0]["reason"]


class TestBadFrames:
    def test_gate_validation_fails_alone(self, plan):
        eng = _engine(plan)
        bad = _frames(2).at[0, 0, 0, 0].set(jnp.nan)
        good_req = eng.submit(_frames(2))
        bad_req = eng.submit(bad)
        eng.flush()
        with pytest.raises(InvalidRequest, match="NaN/Inf"):
            bad_req.result()
        np.testing.assert_allclose(
            np.asarray(good_req.result()),
            np.asarray(plan(_frames(2))),
            rtol=1e-4, atol=1e-5,
        )
        st = eng.stats()
        assert st.n_invalid == 1 and st.n_ok == 1

    def test_wrong_dtype_fails_alone(self, plan):
        eng = _engine(plan)
        h, w = TOPO.input_shape
        req = eng.submit(jnp.zeros((1, h, w, TOPO.input_channels), jnp.int32))
        with pytest.raises(InvalidRequest, match="floating"):
            req.result()

    def test_poisoned_batch_is_isolated(self, plan):
        # With the gate off, a NaN frame reaches the packed batch; the
        # engine detects the poisoned output, reruns requests isolated,
        # and only the invalid request fails.
        eng = _engine(plan, validate=False)
        bad = _frames(2).at[1, 3, 3, 0].set(jnp.nan)
        good_req = eng.submit(_frames(2))
        bad_req = eng.submit(bad)
        eng.flush()
        with pytest.raises(InvalidRequest, match="isolated"):
            bad_req.result()
        np.testing.assert_allclose(
            np.asarray(good_req.result()),
            np.asarray(plan(_frames(2))),
            rtol=1e-4, atol=1e-5,
        )
        st = eng.stats()
        assert st.n_invalid == 1 and st.n_ok == 1
        assert st.n_demotions == 0  # isolation, not demotion


class TestDelayedFlushDeadlines:
    def test_stalled_flush_expires_deadlines_only(self, plan):
        eng = _engine(
            plan,
            fault_plan=FaultPlan([DelayedFlush(at=0, times=1, delay_s=0.05)]),
        )
        slo = eng.submit(_frames(2), deadline_ms=5.0)
        free = eng.submit(_frames(2, seed=3))
        eng.flush()
        with pytest.raises(DeadlineExceeded, match="deadline passed"):
            slo.result()
        np.testing.assert_allclose(
            np.asarray(free.result()),
            np.asarray(plan(_frames(2, seed=3))),
            rtol=1e-4, atol=1e-5,
        )
        st = eng.stats()
        assert st.n_deadline_exceeded == 1 and st.n_ok == 1


class TestAdmissionUnderChaos:
    def test_reject_policy(self, plan):
        eng = _engine(plan, max_queue=1, admission="reject")
        r1 = eng.submit(_frames(1))
        r2 = eng.submit(_frames(1))
        with pytest.raises(Rejected, match="queue full"):
            r2.result()
        assert r1.result().shape == (1, TOPO.n_classes)
        assert eng.stats().n_rejected == 1

    def test_shed_oldest_policy(self, plan):
        eng = _engine(plan, max_queue=1, admission="shed_oldest")
        r1 = eng.submit(_frames(1))
        r2 = eng.submit(_frames(1, seed=4))
        with pytest.raises(Shed, match="shed by newer work"):
            r1.result()
        np.testing.assert_allclose(
            np.asarray(r2.result()),
            np.asarray(plan(_frames(1, seed=4))),
            rtol=1e-4, atol=1e-5,
        )
        st = eng.stats()
        assert st.n_shed == 1 and st.n_ok == 1


# ---------------------------------------------------------------------------
# Chaos on the mesh rung (runs under the CI chaos job's 8 forced host
# devices; skipped on single-device runs).


def _mesh_engine(n_stages=2, **kw):
    params = init_cnn(jax.random.PRNGKey(0), TOPO)
    plan = compile_dhm(TOPO, params, n_stages=n_stages)
    mesh = make_pipeline_mesh((n_stages,), ("stage",))
    eng = Engine(
        plan, microbatch=2, mesh=mesh, n_microbatches=2,
        retry_backoff_s=1e-4, **kw,
    )
    return plan, eng


@pytest.mark.skipif(
    len(jax.devices()) < 2, reason="mesh chaos needs >= 2 devices"
)
class TestMeshChaos:
    def test_device_loss_demotes_to_single_device(self):
        plan, eng = _mesh_engine(
            fault_plan=FaultPlan([DeviceLoss(at=0, times=None, rung="mesh")])
        )
        assert eng.rung == "mesh"
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        assert eng.rung == "fused"
        assert eng.demotions[0]["rung"] == "mesh"

    def test_stalled_collective_times_out_and_demotes(self):
        plan, eng = _mesh_engine(
            fault_plan=FaultPlan(
                [StalledDispatch(at=0, times=None, stall_s=5.0, rung="mesh")]
            ),
            dispatch_timeout_s=0.3,
        )
        x = _frames(4)
        got = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(plan(x)), rtol=1e-4, atol=1e-5
        )
        assert eng.rung == "fused"
        assert eng.stats().n_demotions == 1
