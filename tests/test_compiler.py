"""Tests for the DHM compiler: compile-time validation, end-to-end
equivalence of compiled plans vs the hand-composed reference (all three
paper topologies, fp32 + quantized + pow2), the in-kernel feature-stream
quantization, structural single-matmul guarantees on the compiler path,
and the pipelined executor matching the single-device plan."""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.compiler import (
    CompiledDHM,
    QuantSpec,
    compile_dhm,
    emit_conv_stage,
    validate_topology,
)
from repro.models.cnn import (
    CNNTopology,
    ConvLayerSpec,
    EXTRA_TOPOLOGIES,
    LENET5,
    PAPER_TOPOLOGIES,
    cnn_apply,
    cnn_apply_reference,
    init_cnn,
)


# The ONE jaxpr-walking helper pair, shared with the static-analysis
# engine (tests and the `repro.analysis` CLI can never drift apart).
from repro.analysis.jaxpr_utils import (  # noqa: E402
    count_primitive as _count_primitive,
    count_primitive_in_pallas as _count_primitive_in_pallas,
)


def _mk_inputs(topo, seed=4, batch=2):
    params = init_cnn(jax.random.PRNGKey(seed - 1), topo)
    h, w = topo.input_shape
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (batch, h, w, topo.input_channels)
    )
    return params, x


class TestValidation:
    def _topo(self, **layer_kw):
        return CNNTopology(
            name="bad", input_hw=12, input_channels=2,
            conv_layers=(ConvLayerSpec(n_out=4, kernel=3, **layer_kw),),
            fc_dims=(), n_classes=2,
        )

    def test_typo_act_raises_at_compile_time(self):
        """A typo'd act raises a ValueError naming the options from
        compile_dhm — not a KeyError deep inside a kernel trace."""
        topo = self._topo(act="rleu")
        params = init_cnn(jax.random.PRNGKey(0), topo)
        with pytest.raises(ValueError, match="rleu.*none.*relu.*tanh"):
            compile_dhm(topo, params)

    def test_typo_padding_raises(self):
        with pytest.raises(ValueError, match="SMAE"):
            validate_topology(self._topo(padding="SMAE"))

    def test_bad_pool_raises(self):
        with pytest.raises(ValueError, match="pool"):
            validate_topology(self._topo(pool=-1))

    def test_bad_pool_stride_raises(self):
        with pytest.raises(ValueError, match="pool_stride"):
            validate_topology(self._topo(pool=3, pool_stride=0))
        # pool_stride without pooling is a spec contradiction, not silence.
        with pytest.raises(ValueError, match="pool_stride"):
            validate_topology(self._topo(pool=0, pool_stride=2))

    def test_bad_conv_stride_raises(self):
        with pytest.raises(ValueError, match="stride"):
            validate_topology(self._topo(stride=0))

    def test_oversized_pool_window_raises(self):
        """A pool window larger than the conv output raises at compile
        time instead of silently emitting an empty frame."""
        # 12x12 input, VALID k=3 -> 10x10 conv out; 11x11 pool impossible.
        with pytest.raises(ValueError, match="too small"):
            validate_topology(self._topo(pool=11))

    def test_empty_conv_output_raises(self):
        topo = CNNTopology(
            name="bad", input_hw=4, input_channels=1,
            conv_layers=(
                ConvLayerSpec(n_out=2, kernel=7, padding="VALID", pool=0),
            ),
            fc_dims=(), n_classes=2,
        )
        with pytest.raises(ValueError, match="empty"):
            validate_topology(topo)

    def test_cnn_apply_validates_too(self):
        """The model entry point inherits compile-time validation."""
        topo = self._topo(act="rleu")
        params = init_cnn(jax.random.PRNGKey(0), topo)
        x = jnp.ones((1, 12, 12, 2))
        with pytest.raises(ValueError, match="unknown act"):
            cnn_apply(params, topo, x)

    def test_emit_conv_stage_validates(self):
        import types

        spec = types.SimpleNamespace(padding="SAME", act="relu", pool=-2)
        with pytest.raises(ValueError, match="pool"):
            emit_conv_stage((spec,))

    def test_unknown_backend_raises(self):
        params, _ = _mk_inputs(LENET5)
        with pytest.raises(ValueError, match="unknown backend"):
            compile_dhm(LENET5, params, backend="palas")

    def test_bad_n_stages_raises(self):
        params, _ = _mk_inputs(LENET5)
        with pytest.raises(ValueError, match="n_stages"):
            compile_dhm(LENET5, params, n_stages=3)  # LeNet5 has 2 conv layers

    def test_bad_quant_bits_raise(self):
        with pytest.raises(ValueError, match="act_bits"):
            QuantSpec(act_bits=1)
        with pytest.raises(ValueError, match="weight_bits"):
            QuantSpec(weight_bits=0)


class TestLoweringArtifacts:
    def test_plan_carries_graph_and_assignment(self):
        """The plan exposes the IR it lowered through: the paper-granularity
        DPN and the min-max stage assignment costed from actor payloads."""
        params, _ = _mk_inputs(LENET5)
        plan = compile_dhm(LENET5, params, n_stages=2)
        assert isinstance(plan, CompiledDHM)
        assert plan.graph.total_multipliers() == LENET5.n_multipliers()
        assert plan.assignment.n_stages == 2
        # Stage costs come from the actor FLOP payloads: together they
        # cover every actor in the graph (conv engines + neuron sums +
        # activations + pools — slightly above the bare MAC workload).
        assert sum(s.cost_flops for s in plan.stages) == pytest.approx(
            plan.graph.total_flops()
        )
        assert sum(s.cost_flops for s in plan.stages) == pytest.approx(
            LENET5.feature_extractor_ops(), rel=0.05
        )
        assert [s.conv_layers for s in plan.stages] == [(0,), (1,)]

    def test_stage_partition_is_contiguous_cover(self):
        params, _ = _mk_inputs(PAPER_TOPOLOGIES["cifar10"])
        plan = compile_dhm(PAPER_TOPOLOGIES["cifar10"], params, n_stages=2)
        covered = [i for s in plan.stages for i in s.conv_layers]
        assert covered == list(range(len(plan.topo.conv_layers)))


class TestEndToEndEquivalence:
    """CompiledDHM logits vs the hand-composed cnn_apply_reference, for all
    three paper topologies."""

    @pytest.mark.parametrize(
        "name",
        [
            "lenet5",
            # The CIFAR-sized interpret-mode runs dominate tier-1 wall
            # time; the fast tier keeps the LeNet5 oracle coverage.
            pytest.param("cifar10", marks=pytest.mark.slow),
            pytest.param("svhn", marks=pytest.mark.slow),
        ],
    )
    def test_fp32_oracle_backend_matches_reference(self, name):
        """fp32 plan through the Pallas-interpreter oracle backend."""
        topo = PAPER_TOPOLOGIES[name]
        params, x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(topo, params, backend="pallas_interpret")
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-5
        )

    @pytest.mark.parametrize("name", sorted(PAPER_TOPOLOGIES))
    def test_fp32_compiled_backend_matches_reference(self, name):
        topo = PAPER_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params, backend="pallas")
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("name", sorted(PAPER_TOPOLOGIES))
    def test_quantized_plan_matches_fake_quant_reference(self, name):
        """Quantized plan (weights + in-kernel feature stream) vs the
        model-level fake-quant composition, at the paper's bit-widths."""
        bits = {"lenet5": 3, "cifar10": 6, "svhn": 6}[name]
        topo = PAPER_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=bits, act_bits=bits),
            backend="pallas",
        )
        ref = cnn_apply_reference(
            params, topo, x, weight_bits=bits, act_bits=bits
        )
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_pow2_packed_head_matches_projected_reference(self):
        """quant.pow2_weights lowers the FC head through the packed
        pow2_matmul kernel; logits must match the reference that computes
        x @ project_pow2(w) densely."""
        params, x = _mk_inputs(LENET5)
        plan = compile_dhm(
            LENET5, params, quant=QuantSpec(pow2_weights=True),
            backend="pallas",
        )
        ref = cnn_apply_reference(params, LENET5, x, pow2_weights=True)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-3
        )

    def test_pow2_packed_head_keeps_ste_gradients(self):
        """The packed forward must not kill pow2 QAT: grads reach every
        parameter (straight-through, as with project_pow2_ste)."""
        params, x = _mk_inputs(LENET5)

        def loss(p):
            return jnp.sum(cnn_apply(p, LENET5, x, pow2_weights=True) ** 2)

        g = jax.grad(loss)(params)
        for leaf in jax.tree_util.tree_leaves(g):
            assert bool(jnp.all(jnp.isfinite(leaf)))
        fc_w_grad = g["fc"][0]["w"]
        assert float(jnp.max(jnp.abs(fc_w_grad))) > 0.0

    def test_cnn_apply_is_the_compiled_plan(self):
        """cnn_apply runs the compiled plan's closures: one lowering path,
        no separate hand-wired composition left in the model. (cnn_apply
        stays eager — a fresh plan per call must not retrace a per-plan
        jit — so it is bitwise the plan's stage/head composition and
        allclose to the jitted ``plan(x)``, which XLA re-associates.)"""
        params, x = _mk_inputs(LENET5)
        plan = compile_dhm(LENET5, params, backend="ref")
        out = cnn_apply(params, LENET5, x)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(plan.head_fn(plan.features(x)))
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plan(x)), rtol=1e-5, atol=1e-6
        )

    def test_n_stages_does_not_change_logits(self):
        topo = PAPER_TOPOLOGIES["cifar10"]
        params, x = _mk_inputs(topo)
        one = compile_dhm(topo, params, n_stages=1)(x)
        three = compile_dhm(topo, params, n_stages=3)(x)
        np.testing.assert_allclose(
            np.asarray(one), np.asarray(three), rtol=1e-5, atol=1e-6
        )


class TestGeneralizedTopologies:
    """The non-paper topologies — cifar10_full (overlapping 3x3/stride-2
    pool) and cifar10_strided (stride-2 downsampling convs) — lower
    through compile_dhm on all three backends, matching the hand-composed
    reference exactly."""

    @pytest.mark.parametrize("name", sorted(EXTRA_TOPOLOGIES))
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_fp32_matches_reference(self, name, backend):
        topo = EXTRA_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params, backend=backend)
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.slow
    @pytest.mark.parametrize("name", sorted(EXTRA_TOPOLOGIES))
    def test_fp32_oracle_backend_matches_reference(self, name):
        """The exact Pallas kernel program (interpreter oracle) handles the
        generalized layer vocabulary end to end."""
        topo = EXTRA_TOPOLOGIES[name]
        params, x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(topo, params, backend="pallas_interpret")
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-5
        )

    @pytest.mark.parametrize("name", sorted(EXTRA_TOPOLOGIES))
    def test_quantized_plan_matches_fake_quant_reference(self, name):
        """Weights + in-kernel feature-stream quantization through the
        generalized epilogue (overlapping pool / strided conv)."""
        topo = EXTRA_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(
            topo, params, quant=QuantSpec(weight_bits=6, act_bits=6),
            backend="pallas",
        )
        ref = cnn_apply_reference(params, topo, x, weight_bits=6, act_bits=6)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_block_w_does_not_change_logits(self):
        """Width blocking is a pure tiling decision: a plan compiled with
        a small block_w (column halo exercised) produces the same numbers
        as the unblocked plan, through the kernel oracle."""
        topo = CNNTopology(
            name="wide", input_hw=(10, 26), input_channels=2,
            conv_layers=(
                ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=3,
                              pool_stride=2, act="relu"),
            ),
            fc_dims=(), n_classes=2,
        )
        params, x = _mk_inputs(topo, batch=1)
        full = compile_dhm(topo, params, backend="pallas_interpret")(x)
        blocked = compile_dhm(
            topo, params, backend="pallas_interpret", block_w=4
        )(x)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(blocked))

    def test_rectangular_input_plan(self):
        """(H, W) input frames lower end to end (no square assumption left
        on the compiler path)."""
        topo = CNNTopology(
            name="rect", input_hw=(14, 18), input_channels=2,
            conv_layers=(
                ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=3,
                              pool_stride=2, act="relu"),
                ConvLayerSpec(n_out=6, kernel=3, padding="SAME", stride=2,
                              pool=0, act="relu"),
            ),
            fc_dims=(8,), n_classes=3,
        )
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params, backend="pallas")
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


class TestFusedStreamQuant:
    """The act_bits feature-stream quantization lives inside the fused
    kernel epilogue and agrees with fake_quant_ste on every backend."""

    @pytest.mark.parametrize("backend", ["pallas", "pallas_interpret", "ref"])
    def test_matches_fake_quant_ste_reference(self, backend):
        from repro.core.quant.fixed_point import FixedPointSpec, fake_quant_ste
        from repro.kernels.stream_conv import (
            stream_conv_block,
            stream_conv_block_ref,
        )

        kx, kw, kb = jax.random.split(jax.random.PRNGKey(7), 3)
        x = jax.random.normal(kx, (2, 13, 13, 3))
        w = jax.random.normal(kw, (5, 5, 3, 8)) * 0.2
        b = jax.random.normal(kb, (8,)) * 0.1
        out = stream_conv_block(
            x, w, b, padding="SAME", act="relu", pool=2, act_bits=4,
            backend=backend,
        )
        unquant = stream_conv_block_ref(
            x, w, b, padding="SAME", act="relu", pool=2
        )
        ref = fake_quant_ste(unquant, FixedPointSpec(bits=4, frac_bits=2))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5
        )

    def test_quant_is_inside_the_kernel(self):
        """Structural: with act_bits set, the rounding happens inside the
        pallas_call body (fused epilogue), with no separate post-conv quant
        pass in the surrounding graph."""
        from repro.kernels.stream_conv import stream_conv_block

        x = jnp.ones((1, 16, 16, 3))
        w = jnp.ones((5, 5, 3, 8))
        b = jnp.ones((8,))
        jaxpr = jax.make_jaxpr(
            lambda a, ww, bb: stream_conv_block(
                a, ww, bb, padding="SAME", act="relu", pool=2, act_bits=4,
                backend="pallas_interpret",
            )
        )(x, w, b).jaxpr
        total = _count_primitive(jaxpr, "round")
        inside = _count_primitive_in_pallas(jaxpr, "round")
        assert inside == 1
        assert total == inside  # nothing quantizes the stream outside

    def test_compiled_plan_uses_in_kernel_quant(self):
        """The whole quantized plan traces with its only feature-stream
        rounding inside pallas_call bodies (one per conv stage) —
        enforced through the static-analysis registry (invariant V007),
        so this test and the CLI gate can never drift apart."""
        from repro.analysis.verify import verify_plan

        topo = LENET5
        params, _x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(
            topo, params, quant=QuantSpec(act_bits=4),
            backend="pallas_interpret",
        )
        assert verify_plan(plan, ids=("V007",)) == []


class TestStructureCompilerPath:
    """The structural single-matmul guarantee carries over to the compiler
    path: a compiled conv stage still traces to exactly ONE dot_general per
    row block and zero lax.conv."""

    @pytest.mark.parametrize("backend", ["pallas", "pallas_interpret"])
    def test_single_matmul_per_row_block(self, backend):
        topo = CNNTopology(
            name="one", input_hw=32, input_channels=3,
            conv_layers=(
                ConvLayerSpec(n_out=32, kernel=5, padding="SAME", pool=2,
                              act="relu"),
            ),
            fc_dims=(), n_classes=2,
        )
        from repro.analysis.verify import verify_plan

        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = compile_dhm(topo, params, backend=backend)
        # one conv layer -> exactly one dot_general and zero lax.conv:
        # registry invariants V001/V003 (same checks the CLI gate runs)
        assert verify_plan(plan, ids=("V001", "V003")) == []

    def test_make_conv_stage_is_compiler_emitted(self):
        """The pipeline stage-body builder and emit_conv_stage produce the
        same computation (one lowering path for stage bodies)."""
        import types

        from repro.core.dhm.pipeline import make_conv_stage

        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        params = {
            "w": jax.random.normal(k1, (3, 3, 4, 4)) * 0.3,
            "b": jnp.zeros((4,)),
        }
        x = jax.random.normal(k2, (2, 8, 8, 4))
        via_pipeline = make_conv_stage(padding="SAME", act="tanh", pool=0)
        spec = types.SimpleNamespace(padding="SAME", act="tanh", pool=0)
        via_compiler = emit_conv_stage((spec,))
        np.testing.assert_array_equal(
            np.asarray(via_pipeline(params, x)),
            np.asarray(via_compiler([params], x)),
        )


PIPELINE_PLAN_SUBPROCESS = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.dhm.compiler import compile_dhm
from repro.core.dhm.pipeline import make_pipeline_mesh
from repro.models.cnn import LENET5, init_cnn

# LeNet5 with 2 stages is genuinely heterogeneous (28x28x1 -> 12x12x20 ->
# 4x4x50): the old executor refused it; the boxed executor streams it
# bit-exact vs the single-device plan at the same batch grain.
plan = compile_dhm(LENET5, init_cnn(jax.random.PRNGKey(0), LENET5), n_stages=2)
mbs = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 28, 28, 1))
seq = jnp.stack([plan.features(mbs[i]) for i in range(4)])
out = plan.run_pipelined(mbs, mesh=make_pipeline_mesh((2,), ('stage',)))
assert (np.asarray(out) == np.asarray(seq)).all(), 'stage-mesh plan mismatch'
# 2D (stage, data) mesh: batch sharding composes with the stage pipeline.
mesh2 = make_pipeline_mesh((2, 2), ('stage', 'data'))
out2 = plan.run_pipelined(mbs, mesh=mesh2, data_axis='data')
assert np.allclose(np.asarray(out2), np.asarray(seq), atol=1e-5), '2D mismatch'
print('OK')
"""


class TestPipelinedPlan:
    def test_heterogeneous_stages_emit_pipeline_spec(self):
        """Heterogeneous stages pipeline now: the plan emits per-stage
        closures + chaining StageIOSpec geometry instead of raising."""
        params, _ = _mk_inputs(LENET5)
        plan = compile_dhm(LENET5, params, n_stages=2)
        fns, stage_params, io = plan.pipeline_spec()
        assert len(fns) == 2 and len(stage_params) == 2
        assert io[0].in_shape == (28, 28, 1)
        assert io[0].out_shape == io[1].in_shape == (12, 12, 20)
        assert io[1].out_shape == (4, 4, 50)

    @pytest.mark.slow
    def test_pipelined_plan_matches_single_device_4dev(self):
        """The compiled heterogeneous staged plan on a forced-host-device
        mesh == the same plan run sequentially on one device (subprocess
        with forced host devices)."""
        repo_root = pathlib.Path(__file__).resolve().parents[1]
        res = subprocess.run(
            [sys.executable, "-c", PIPELINE_PLAN_SUBPROCESS],
            capture_output=True,
            text=True,
            env={
                **os.environ,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": str(repo_root / "src"),
            },
            cwd=str(repo_root),
            timeout=600,
        )
        assert res.returncode == 0, res.stderr[-2000:]
        assert "OK" in res.stdout


class TestPow2OddWidth:
    """Satellite bugfix: odd output widths pack via an auto-pad instead of
    raising (kernel wrapper) or being silently skipped (serving walk)."""

    def test_quantize_weights_odd_n(self):
        from repro.core.quant.pow2 import project_pow2
        from repro.kernels.pow2_matmul import pow2_matmul, quantize_weights

        kx, kw = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(kx, (9, 13))
        w = jax.random.normal(kw, (13, 7))
        packed, scale = quantize_weights(w)
        assert scale.shape == (7,)
        assert packed.shape == (13, 4)  # ceil(7/2) bytes
        for backend in ("ref", "pallas", "pallas_interpret"):
            out = pow2_matmul(
                x, packed, scale, block_m=8, block_n=8, block_k=8,
                backend=backend,
            )
            assert out.shape == (9, 7)
            np.testing.assert_allclose(
                np.asarray(out),
                np.asarray(x @ project_pow2(w, channel_axis=1)),
                rtol=1e-4, atol=1e-4,
            )

    def test_inconsistent_packed_scale_raises(self):
        from repro.kernels.pow2_matmul import pow2_matmul

        x = jnp.ones((4, 6))
        packed = jnp.zeros((6, 2), jnp.uint8)  # 4 columns
        scale = jnp.ones((7,))  # claims 7
        with pytest.raises(ValueError, match="inconsistent"):
            pow2_matmul(x, packed, scale)

    def test_linear_pack_odd_n(self):
        from repro.core.quant.pow2 import project_pow2
        from repro.models.layers import linear, pack_linear_pow2

        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        p = {"w": jax.random.normal(k1, (12, 7)), "b": jnp.ones((7,))}
        x = jax.random.normal(k2, (3, 12))
        out = linear(x, pack_linear_pow2(p))
        ref = x @ project_pow2(p["w"], channel_axis=1) + p["b"]
        assert out.shape == (3, 7)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )
