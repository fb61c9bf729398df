import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

"""The paper, end to end: train LeNet5, pick the bit-width, classify the
constants, estimate FPGA resources under all three multiplier strategies,
report DHM throughput — then run the TPU analogue through the DHM
compiler: build a topology, ``compile_dhm`` it (topology -> DPN -> stages
-> fused-kernel plan, quantization baked in), and run the plan either
single-device or as a 4-stage spatial pipeline (shard_map + ppermute).

    PYTHONPATH=src python examples/dhm_cnn.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.dhm import (
    CYCLONE_V_5CGXFC9E7,
    MultiplierStrategy,
    QuantSpec,
    balance_report,
    cnn_to_dpn,
    compile_dhm,
    dhm_throughput_gops,
    estimate_resources,
)
from repro.core.dhm.pipeline import make_pipeline_mesh
from repro.core.dhm.resources import ParamClassFractions
from repro.models.cnn import LENET5, cnn_apply_reference
from repro.paper.analysis import classify_model
from repro.paper.train_cnn import get_trained_cnn


def main():
    print("== 1. Train + quantize (paper §4.1) ==")
    trained = get_trained_cnn("lenet5")
    print(f"LeNet5 float accuracy (synthetic task): "
          f"{trained.float_accuracy:.3f}")
    bits = 3
    stats = classify_model(trained.params, bits)
    print(f"param classes @ {bits}b: zero={100*stats.zero:.1f}% "
          f"one={100*stats.one:.1f}% pow2={100*stats.pow2:.1f}% "
          f"other={100*stats.other:.1f}% -> "
          f"{100*stats.multiplierless:.1f}% multiplierless")

    print("\n== 2. DHM resource mapping (paper §4.2, Tables 2-3) ==")
    g = cnn_to_dpn(LENET5, bits=bits)
    print(f"DPN: {len(g.actors)} actors, {g.total_multipliers()} multipliers,"
          f" {g.total_line_buffer_bits()} line-buffer bits")
    fr = ParamClassFractions(stats.zero, stats.one, stats.pow2, stats.other)
    for strat in MultiplierStrategy:
        rep = estimate_resources(
            g, CYCLONE_V_5CGXFC9E7, bits=bits, strategy=strat,
            fractions=fr,
        )
        print("  " + rep.summary())

    print("\n== 3. DHM throughput (paper Table 4) ==")
    print("  " + dhm_throughput_gops(LENET5, 65.71).summary())

    print("\n== 4. Compile: topology -> DPN -> stages -> fused plan ==")
    # The whole TPU mapping is now one pass: compile_dhm expands the
    # topology to the paper-granularity actor graph, partitions it with the
    # min-max DP mapper (costed from actor FLOP payloads), and emits fused
    # conv->bias->act(->pool->quant) kernel closures per stage, with the
    # paper's 3-bit quantization baked into the plan.
    plan = compile_dhm(
        LENET5, trained.params,
        quant=QuantSpec(weight_bits=bits, act_bits=bits),
        n_stages=2,
    )
    br = balance_report(
        [s.cost_flops for s in plan.stages], plan.n_stages, n_microbatches=8
    )
    print(f"  {plan.n_stages} stages over {len(plan.conv_params)} conv "
          f"layers: boundaries {plan.assignment.boundaries}, bottleneck "
          f"{plan.assignment.bottleneck/1e3:.0f}k flops, pipeline "
          f"efficiency {br.pipeline_efficiency:.2f}")
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 28, 28, 1)), jnp.float32
    )
    ref = cnn_apply_reference(trained.params, LENET5, x,
                              weight_bits=bits, act_bits=bits)
    logits = plan(x)
    logits.block_until_ready()
    t0 = time.time()
    for _ in range(5):
        out = plan(x)
    out.block_until_ready()
    us = (time.time() - t0) / 5 * 1e6
    ok = np.allclose(np.asarray(logits), np.asarray(ref), atol=1e-4)
    print(f"  quantized compiled plan {tuple(x.shape)} -> "
          f"{tuple(logits.shape)}: matches fake-quant reference={ok}, "
          f"{us:.0f} us/call ({8 / (us * 1e-6):.0f} frames/s)")

    print("\n== 4b. Generalized layer vocabulary: cifar10_full ==")
    # Beyond the paper's three nets: Caffe's cifar10_full uses OVERLAPPING
    # 3x3/stride-2 pooling (window != stride). The same compile_dhm pass
    # lowers it — generalized fused epilogue, pool-aware row blocking —
    # with no topology-specific code.
    from repro.models.cnn import CIFAR10_FULL, init_cnn as _init

    full_params = _init(jax.random.PRNGKey(2), CIFAR10_FULL)
    full_plan = compile_dhm(
        CIFAR10_FULL, full_params, quant=QuantSpec(weight_bits=6, act_bits=6)
    )
    xf = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 32, 3))
    ref_f = cnn_apply_reference(full_params, CIFAR10_FULL, xf,
                                weight_bits=6, act_bits=6)
    ok = np.allclose(np.asarray(full_plan(xf)), np.asarray(ref_f), atol=1e-4)
    shapes = " -> ".join(
        f"{h}x{w}" for (_, _, _, h, w) in CIFAR10_FULL.conv_shapes()
    )
    print(f"  cifar10_full (3x3/stride-2 overlapping pool, conv dims "
          f"{shapes}): quantized plan matches reference={ok}")

    print("\n== 5. THE SAME LeNet5 plan, spatial pipeline on a mesh ==")
    # The quantized LeNet5 plan from step 4 — heterogeneous stages
    # (28x28x1 -> 12x12x20 -> 4x4x50) — streams through the spatial
    # pipeline directly: each stage gets a private device group (DHM:
    # private resources per actor), activations flow over boxed ICI edges
    # sized from the compiler's per-stage StageIOSpec, and a 2D
    # (stage, data) mesh adds data-parallel batch sharding on top.
    for st in plan.stages:
        print(f"  stage {st.index}: {st.io.in_shape} -> {st.io.out_shape}")
    mesh = make_pipeline_mesh((2, 2), ("stage", "data"))
    mbs = jnp.asarray(
        np.random.default_rng(1).normal(size=(6, 4, 28, 28, 1)), jnp.float32
    )
    t0 = time.time()
    out = plan.run_pipelined(mbs, mesh=mesh, data_axis="data")
    seq = jnp.stack([plan.features(mbs[i]) for i in range(6)])
    ok = np.allclose(np.asarray(out), np.asarray(seq), atol=1e-5)
    print(f"  2-stage heterogeneous pipeline on (2 stage x 2 data): "
          f"matches single-device plan={ok} ({time.time()-t0:.2f}s, "
          f"bubble={plan.n_stages-1}/{6+1} ticks)")

    print("\n== 6. Serving engine: µbatch queue over the same plan ==")
    # The Engine is the serving front end: requests queue up, get packed
    # into fixed micro-batches, and run through the plan's DONATED jitted
    # closure (double-buffered under async dispatch); stats track
    # per-request latency and engine throughput.
    from repro.core.dhm import Engine

    eng = Engine(plan, microbatch=8)
    reqs = [
        eng.submit(jnp.asarray(
            np.random.default_rng(10 + i).normal(
                size=(np.random.default_rng(20 + i).integers(1, 6),
                      28, 28, 1)
            ), jnp.float32,
        ))
        for i in range(5)
    ]
    eng.flush()
    total = sum(r.result().shape[0] for r in reqs)
    print(f"  served {len(reqs)} requests ({total} frames); "
          f"{eng.stats().summary()}")
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
