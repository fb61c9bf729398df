import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""CNN serving demo: compile a topology once, stand up the serving Engine,
and stream inference requests through it — single-device (micro-batch
queue + double-buffered donated closures), fault-tolerant (deadline SLOs
through the background flusher, admission control, injected faults healed
by retry or one-rung demotion), multi-tenant (two plans behind one
Router: a faulted tenant trips its circuit breaker while the other's
SLOs hold, then a verified hot swap + rollback), and spatially
pipelined on a (stage, data) host-device mesh (every compiled stage
owns a private device group; heterogeneous activations flow over boxed
ICI edges).

    PYTHONPATH=src python examples/serve_cnn.py
    PYTHONPATH=src python examples/serve_cnn.py --topology cifar10_full \
        --bits 6 --requests 32
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.dhm import Engine, QuantSpec, Router, compile_dhm
from repro.core.dhm.faults import DispatchError, FaultPlan, NaNActivation
from repro.core.dhm.pipeline import make_pipeline_mesh
from repro.models.cnn import ALL_TOPOLOGIES, init_cnn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="cifar10",
                    choices=sorted(ALL_TOPOLOGIES))
    ap.add_argument("--bits", type=int, default=0,
                    help="fixed-point bits for weights + feature stream "
                         "(0 = fp32)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--microbatch", type=int, default=8)
    ap.add_argument("--stages", type=int, default=0,
                    help="pipeline stages for the mesh engine "
                         "(0 = one per conv layer, capped at 3)")
    args = ap.parse_args()

    topo = ALL_TOPOLOGIES[args.topology]
    quant = (
        QuantSpec(weight_bits=args.bits, act_bits=args.bits)
        if args.bits else QuantSpec()
    )
    params = init_cnn(jax.random.PRNGKey(0), topo)
    rng = np.random.default_rng(0)
    h, w = topo.input_shape

    def random_request(i):
        n = int(rng.integers(1, args.microbatch + 1))
        return jnp.asarray(
            rng.normal(size=(n, h, w, topo.input_channels)), jnp.float32
        )

    print(f"== single-device engine: {topo.name}, "
          f"{'fp32' if not args.bits else f'{args.bits}-bit'} plan ==")
    plan = compile_dhm(topo, params, quant=quant)
    eng = Engine(plan, microbatch=args.microbatch)
    reqs = [eng.submit(random_request(i)) for i in range(args.requests)]
    eng.flush()
    total = sum(r.result().shape[0] for r in reqs)
    x0 = random_request(0)
    np.testing.assert_allclose(
        np.asarray(eng.infer(x0)), np.asarray(plan(x0)), rtol=1e-4, atol=1e-4
    )
    print(f"  served {len(reqs)} requests / {total} frames, logits match "
          f"the plan; {eng.stats().summary()}")

    print("\n== SLO serving: background flusher, 25 ms deadlines, "
          "shed-oldest admission ==")
    with Engine(
        plan, microbatch=args.microbatch, auto_flush=True,
        max_queue=2 * args.requests, admission="shed_oldest",
        default_deadline_ms=25.0,
    ) as slo_eng:
        slo_reqs = [slo_eng.submit(random_request(i))
                    for i in range(args.requests)]
        done, missed = [], []
        for r in slo_reqs:
            try:
                r.result(timeout=30.0)
                done.append(r)
            except Exception as e:      # DeadlineExceeded / Shed: structured
                missed.append(f"{type(e).__name__}: {e}")
    print(f"  {len(done)}/{len(slo_reqs)} requests met their SLO; "
          f"{slo_eng.stats().summary()}")
    for msg in missed[:3]:
        print(f"  missed: {msg}")

    print("\n== chaos: injected dispatch errors + NaN activations ==")
    chaos_eng = Engine(
        plan, microbatch=args.microbatch,
        fault_plan=FaultPlan([
            DispatchError(at=0, times=2),     # transient: retry heals
            NaNActivation(at=3, times=1),     # corrupted logits: caught
        ]),
        retry_backoff_s=1e-3,
    )
    for i in range(4):
        xi = random_request(i)
        np.testing.assert_allclose(
            np.asarray(chaos_eng.infer(xi)), np.asarray(plan(xi)),
            rtol=1e-4, atol=1e-4,
        )
    st = chaos_eng.stats()
    print(f"  survived {st.n_retries} injected failures by retry "
          f"(rung: {st.rung}, demotions: {st.n_demotions}); logits verified "
          f"against the plan; {st.summary()}")

    print("\n== chaos: persistent fused-rung failure -> demotion ladder ==")
    demoted_eng = Engine(
        plan, microbatch=args.microbatch,
        fault_plan=FaultPlan(
            [DispatchError(at=0, times=None, rung="fused")]
        ),
        max_retries=1, retry_backoff_s=1e-3,
    )
    np.testing.assert_allclose(
        np.asarray(demoted_eng.infer(x0)), np.asarray(plan(x0)),
        rtol=1e-4, atol=1e-4,
    )
    for d in demoted_eng.demotions:
        print(f"  demoted off rung {d['rung']!r}: {d['reason']}")
    print(f"  now serving on rung {demoted_eng.rung!r}, logits still match "
          f"the healthy plan")

    print("\n== multi-tenant router: two plans, bulkheads, circuit "
          "breaker, hot swap ==")
    lenet = ALL_TOPOLOGIES["lenet5"]
    cifar = ALL_TOPOLOGIES["cifar10"]
    plan_mnist = compile_dhm(lenet, init_cnn(jax.random.PRNGKey(0), lenet))
    plan_cifar = compile_dhm(cifar, init_cnn(jax.random.PRNGKey(0), cifar))

    def tenant_frames(t, n, seed):
        th, tw = t.input_shape
        return jnp.asarray(
            np.random.default_rng(seed).normal(
                size=(n, th, tw, t.input_channels)
            ),
            jnp.float32,
        )

    # Every dispatch of tenant 'mnist' is faulted; 'cifar' is untouched.
    router = Router(
        fault_plan=FaultPlan(
            [DispatchError(at=0, times=None, tenant="mnist")]
        ),
        max_retries=0, allow_degraded=False,
        breaker_threshold=3, breaker_reset_s=60.0,
        microbatch=args.microbatch,
    )
    router.add("mnist", plan_mnist)
    router.add("cifar", plan_cifar)
    with router:
        mnist_errors = 0
        for i in range(6):
            try:
                router.submit(
                    "mnist", tenant_frames(lenet, 2, 10 + i)
                ).result(timeout=30.0)
            except Exception:   # BatchFailed, then CircuitOpen: structured
                mnist_errors += 1
            xc = tenant_frames(cifar, 2, 20 + i)
            np.testing.assert_allclose(
                np.asarray(router.infer("cifar", xc)),
                np.asarray(plan_cifar(xc)), rtol=1e-4, atol=1e-4,
            )
        st_cifar = router.engine("cifar").stats()
        print(f"  tenant 'mnist': {mnist_errors}/6 failed, breaker "
              f"{router.breaker('mnist').state!r} (fails fast, no "
              f"dispatches wasted)")
        print(f"  tenant 'cifar': {st_cifar.n_ok} ok / "
              f"{st_cifar.n_errors} errors — the bulkhead held")

        # Verified hot swap: retrained cifar weights go live with zero
        # dropped requests; a plan that fails verify_plan is refused.
        plan_cifar_v2 = compile_dhm(
            cifar, init_cnn(jax.random.PRNGKey(7), cifar)
        )
        pre_swap = router.submit("cifar", tenant_frames(cifar, 2, 40))
        router.swap("cifar", plan_cifar_v2)
        np.testing.assert_allclose(
            np.asarray(pre_swap.result(timeout=30.0)),
            np.asarray(plan_cifar(tenant_frames(cifar, 2, 40))),
            rtol=1e-4, atol=1e-4,
        )
        x_post = tenant_frames(cifar, 2, 41)
        np.testing.assert_allclose(
            np.asarray(router.infer("cifar", x_post)),
            np.asarray(plan_cifar_v2(x_post)), rtol=1e-4, atol=1e-4,
        )
        print("  hot swap 'cifar' -> v2: in-flight request answered by "
              "the OLD plan, next by the NEW — zero drops")
        try:
            router.swap("cifar", plan_mnist)  # wrong serving surface
        except Exception as e:
            print(f"  swap to incompatible plan refused: "
                  f"{type(e).__name__} (old plan still serving)")
        router.rollback("cifar")
        np.testing.assert_allclose(
            np.asarray(router.infer("cifar", x_post)),
            np.asarray(plan_cifar(x_post)), rtol=1e-4, atol=1e-4,
        )
        print("  rollback 'cifar': v1 weights answering again")

    n_dev = len(jax.devices())
    n_stages = args.stages or min(3, len(topo.conv_layers))
    data = max(1, min(2, n_dev // n_stages))
    if n_stages * data > n_dev or n_stages < 2:
        print(f"\n(skipping mesh engine: need >= {max(2, n_stages) * data} "
              f"devices, have {n_dev})")
        return
    print(f"\n== pipelined engine: ({n_stages} stage x {data} data) mesh, "
          f"{n_dev} host devices ==")
    plan_s = compile_dhm(topo, params, quant=quant, n_stages=n_stages)
    for st in plan_s.stages:
        print(f"  stage {st.index}: {st.io.in_shape} -> {st.io.out_shape} "
              f"({st.cost_flops / 1e6:.2f} Mflop)")
    mesh_axes = (("stage", "data") if data > 1 else ("stage",))
    mesh_shape = (n_stages, data) if data > 1 else (n_stages,)
    mesh = make_pipeline_mesh(mesh_shape, mesh_axes)
    engp = Engine(
        plan_s, microbatch=args.microbatch, mesh=mesh, n_microbatches=4,
        data_axis="data" if data > 1 else None,
    )
    reqs = [engp.submit(random_request(i)) for i in range(args.requests)]
    engp.flush()
    total = sum(r.result().shape[0] for r in reqs)
    np.testing.assert_allclose(
        np.asarray(engp.infer(x0)), np.asarray(plan_s(x0)),
        rtol=1e-4, atol=1e-4,
    )
    print(f"  served {len(reqs)} requests / {total} frames through the "
          f"spatial pipeline, logits match the single-device plan; "
          f"{engp.stats().summary()}")
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
