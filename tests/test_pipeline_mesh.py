"""Multi-device property tests for the heterogeneous spatial pipeline.

These run on a >= 8-device host-platform mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the CI
multi-device job sets the flag and runs this file directly; on a normal
1-device tier-1 run the mesh tests skip and the slow subprocess runner
(``test_mesh_suite_subprocess``) re-launches the file with forced host
devices so the coverage survives everywhere.

The property under test: the GPipe fill/steady/drain executor produces
**bit-exact** outputs vs the single-device ``CompiledDHM`` plan run at
the same batch grain, for heterogeneous stage shapes (pool/stride
shrink, channel growth), fp32 and quantized, across stage counts 2-4,
with data-parallel batch sharding on a 2D ``(stage, data)`` mesh, on
BOTH interior-edge paths (exact shape classes and the boxed max-shape
fallback) and BOTH schedules (serial and overlapped double-buffered
collectives).
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.pipeline import make_pipeline_mesh
from repro.models.cnn import (
    ALL_TOPOLOGIES,
    CHAIN_TOPOLOGIES,
    CNNTopology,
    ConvLayerSpec,
    init_cnn,
)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >= 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

PAPER_BITS = {
    "lenet5": 3, "cifar10": 6, "svhn": 6,
    "cifar10_full": 6, "cifar10_strided": 6,
}

# A 4-conv-layer heterogeneous topology (channel growth, overlapping pool,
# strided conv, rectangular frame) so stage counts up to 4 are exercised.
HET4 = CNNTopology(
    name="het4", input_hw=(20, 24), input_channels=2,
    conv_layers=(
        ConvLayerSpec(n_out=8, kernel=3, padding="SAME", pool=0, act="relu"),
        ConvLayerSpec(n_out=12, kernel=3, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
        ConvLayerSpec(n_out=16, kernel=3, padding="SAME", stride=2, pool=0,
                      act="tanh"),
        ConvLayerSpec(n_out=16, kernel=3, padding="SAME", pool=2, act="relu"),
    ),
    fc_dims=(16,), n_classes=4,
)


def _compile(topo, params, bits, n_stages):
    from repro.core.dhm.compiler import QuantSpec, compile_dhm

    quant = QuantSpec() if bits is None else QuantSpec(
        weight_bits=bits, act_bits=bits
    )
    return compile_dhm(topo, params, quant=quant, n_stages=n_stages)


def _mbs(topo, m=4, mb=2, seed=1):
    h, w = topo.input_shape
    return jax.random.normal(
        jax.random.PRNGKey(seed), (m, mb, h, w, topo.input_channels)
    )


def _seq_features(plan, mbs):
    """Single-device plan at the pipeline's batch grain: one sequential
    run per µbatch (bit-comparable — GEMM blocking depends on the batch
    size, so a merged-batch run is not the same computation)."""
    return jnp.stack([plan.features(mbs[i]) for i in range(mbs.shape[0])])


def _sharded_ref(plan, mbs, D):
    """Single-device reference for a data-sharded pipeline: one run per
    (µbatch, data shard) at the local grain mb/D, shards re-concatenated
    on the batch axis."""
    loc = mbs.shape[1] // D
    return jnp.concatenate(
        [
            jnp.stack(
                [
                    plan.features(mbs[i, d * loc : (d + 1) * loc])
                    for i in range(mbs.shape[0])
                ]
            )
            for d in range(D)
        ],
        axis=1,
    )


@needs_mesh
class TestHeterogeneousPipeline:
    @pytest.mark.parametrize("quant", ["fp32", "quant"])
    @pytest.mark.parametrize("name", sorted(CHAIN_TOPOLOGIES))
    def test_all_topologies_bit_exact(self, name, quant):
        """All five topologies — every one heterogeneous across stages —
        stream through the spatial pipeline on a >= 4-device
        (stage, data) mesh bit-exact vs the single-device plan run at the
        pipeline's local batch grain."""
        topo = ALL_TOPOLOGIES[name]
        n_stages = min(3, len(topo.conv_layers))
        bits = PAPER_BITS[name] if quant == "quant" else None
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, bits, n_stages)
        # Stage shapes genuinely differ (the old executor refused these).
        assert len({st.io.in_shape for st in plan.stages}) > 1
        D, mb = 2, 4
        mbs = _mbs(topo, mb=mb)
        mesh = make_pipeline_mesh((n_stages, D), ("stage", "data"))
        assert n_stages * D >= 4
        out = plan.run_pipelined(mbs, mesh=mesh, data_axis="data")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_sharded_ref(plan, mbs, D))
        )

    @pytest.mark.parametrize("name", sorted(CHAIN_TOPOLOGIES))
    def test_all_topologies_stage_mesh_bit_exact(self, name):
        """Same property on a pure stage mesh (no data sharding): the
        pipelined stream is bitwise the sequential per-µbatch plan."""
        topo = ALL_TOPOLOGIES[name]
        n_stages = min(3, len(topo.conv_layers))
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, PAPER_BITS[name], n_stages)
        mbs = _mbs(topo)
        mesh = make_pipeline_mesh((n_stages,), ("stage",))
        out = plan.run_pipelined(mbs, mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_seq_features(plan, mbs))
        )

    @pytest.mark.parametrize("n_stages", [2, 3, 4])
    @pytest.mark.parametrize("quant", ["fp32", "quant"])
    def test_stage_counts_bit_exact(self, n_stages, quant):
        """Fill/steady/drain is bit-exact across stage counts 2-4 on a
        4-layer topology mixing pool windows, conv stride and channel
        growth."""
        bits = 6 if quant == "quant" else None
        params = init_cnn(jax.random.PRNGKey(0), HET4)
        plan = _compile(HET4, params, bits, n_stages)
        mbs = _mbs(HET4, m=5, mb=2)
        mesh = make_pipeline_mesh((n_stages,), ("stage",))
        out = plan.run_pipelined(mbs, mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_seq_features(plan, mbs))
        )

    def test_data_axis_sharding_bit_exact(self):
        """2D (stage, data) mesh: batch sharding composes with the stage
        pipeline; each data column's shard is bit-exact vs the
        single-device plan run at the local batch grain."""
        topo = ALL_TOPOLOGIES["cifar10"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 3)
        D, mb = 2, 4
        mbs = _mbs(topo, m=3, mb=mb)
        mesh = make_pipeline_mesh((3, D), ("stage", "data"))
        out = plan.run_pipelined(mbs, mesh=mesh, data_axis="data")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_sharded_ref(plan, mbs, D))
        )

    def test_mesh_size_mismatch_raises(self):
        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 2)
        mesh = make_pipeline_mesh((4,), ("stage",))
        with pytest.raises(ValueError, match="mesh axis"):
            plan.run_pipelined(_mbs(topo), mesh=mesh)

    def test_indivisible_data_shard_raises(self):
        topo = ALL_TOPOLOGIES["cifar10"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 3)
        mesh = make_pipeline_mesh((3, 2), ("stage", "data"))
        with pytest.raises(ValueError, match="not divisible"):
            plan.run_pipelined(
                _mbs(topo, mb=3), mesh=mesh, data_axis="data"
            )


@needs_mesh
class TestEdgePaths:
    """The exact-shape and boxed ICI edge paths are interchangeable in
    value space: bit-identical to each other and to the single-device
    plan, for every topology and precision."""

    @pytest.mark.parametrize("quant", ["fp32", "quant"])
    @pytest.mark.parametrize("name", sorted(CHAIN_TOPOLOGIES))
    def test_exact_vs_boxed_bit_identical(self, name, quant):
        topo = ALL_TOPOLOGIES[name]
        n_stages = min(3, len(topo.conv_layers))
        bits = PAPER_BITS[name] if quant == "quant" else None
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, bits, n_stages)
        mbs = _mbs(topo, m=3, mb=2)
        mesh = make_pipeline_mesh((n_stages,), ("stage",))
        exact = plan.run_pipelined(mbs, mesh=mesh, edge_mode="exact")
        boxed = plan.run_pipelined(mbs, mesh=mesh, edge_mode="boxed")
        ref = np.asarray(_seq_features(plan, mbs))
        np.testing.assert_array_equal(np.asarray(exact), ref)
        np.testing.assert_array_equal(np.asarray(boxed), ref)

    @pytest.mark.parametrize("n_microbatches", [1, 2, 3, 6])
    def test_overlap_matches_serial(self, n_microbatches):
        """The overlapped double-buffered schedule computes the same bits
        as the serial schedule at every µbatch count in {1, 2, S, 2S}
        (S=3): only the tick count changes, never the values."""
        topo = ALL_TOPOLOGIES["cifar10"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 3)
        mbs = _mbs(topo, m=n_microbatches, mb=2)
        mesh = make_pipeline_mesh((3,), ("stage",))
        serial = plan.run_pipelined(mbs, mesh=mesh, overlap=False)
        overlapped = plan.run_pipelined(mbs, mesh=mesh, overlap=True)
        ref = np.asarray(_seq_features(plan, mbs))
        np.testing.assert_array_equal(np.asarray(serial), ref)
        np.testing.assert_array_equal(np.asarray(overlapped), ref)

    def test_overlap_with_data_sharding_and_quant(self):
        """Overlap composes with 2D batch sharding and quantized stage
        bodies — still bit-exact at the local grain."""
        topo = ALL_TOPOLOGIES["svhn"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, PAPER_BITS["svhn"], 3)
        D = 2
        mbs = _mbs(topo, m=4, mb=4)
        mesh = make_pipeline_mesh((3, D), ("stage", "data"))
        out = plan.run_pipelined(
            mbs, mesh=mesh, data_axis="data", overlap=True
        )
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_sharded_ref(plan, mbs, D))
        )

    def test_runner_reports_edge_path(self):
        """Structural: the built runner exposes which edge path it took —
        exact shape classes by default (every real topology), the boxed
        max-shape class when forced or when auto exceeds the class
        budget."""
        from repro.core.dhm.engine import build_plan_pipeline
        from repro.core.dhm.pipeline import PipelineConfig

        topo = ALL_TOPOLOGIES["cifar10"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 3)
        mesh = make_pipeline_mesh((3,), ("stage",))
        auto = build_plan_pipeline(
            plan, mesh=mesh, cfg=PipelineConfig(3, 4)
        )
        assert auto.edge_plan.mode == "exact"
        assert auto.edge_plan.n_classes == 2
        assert auto.edge_plan.padding_fraction() == 0.0
        boxed = build_plan_pipeline(
            plan, mesh=mesh, cfg=PipelineConfig(3, 4, edge_mode="boxed")
        )
        assert boxed.edge_plan.mode == "boxed"
        assert boxed.edge_plan.n_classes == 1
        assert boxed.edge_plan.padding_fraction() > 0.0
        squeezed = build_plan_pipeline(
            plan, mesh=mesh, cfg=PipelineConfig(3, 4, max_edge_classes=1)
        )
        assert squeezed.edge_plan.mode == "boxed"


@needs_mesh
class TestEngineOnMesh:
    @pytest.mark.parametrize("quant", ["fp32", "quant"])
    def test_engine_pipelined_matches_single_device(self, quant):
        """The serving Engine's pipelined path (jitted runner closure,
        donated frames, 2D mesh) agrees with the single-device plan."""
        from repro.core.dhm.engine import Engine

        topo = ALL_TOPOLOGIES["lenet5"]
        bits = PAPER_BITS["lenet5"] if quant == "quant" else None
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, bits, 2)
        mesh = make_pipeline_mesh((2, 2), ("stage", "data"))
        eng = Engine(
            plan, microbatch=4, mesh=mesh, n_microbatches=3,
            data_axis="data",
        )
        x = jax.random.normal(jax.random.PRNGKey(3), (12, 28, 28, 1))
        out = eng.infer(x)
        ref = plan(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )
        st = eng.stats()
        assert st.n_frames == 12 and st.frames_per_busy_s > 0

    def test_engine_tuned_config(self):
        """A PipelineTuning overrides the engine's pipeline knobs
        (µbatch count, grain, overlap, edge path) and the served logits
        still match the single-device plan."""
        from repro.core.dhm.engine import Engine
        from repro.core.dhm.throughput import autotune_pipeline

        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 2)
        measured = [{
            "n_stages": 2, "n_microbatches": 2, "microbatch": 4,
            "data": 2, "overlap": True, "edge_mode": "boxed",
            "frames_per_s": 123.0,
        }]
        tuning = autotune_pipeline(plan, 4, measurements=measured)
        assert tuning.source == "measured" and tuning.overlap
        mesh = make_pipeline_mesh((2, 2), ("stage", "data"))
        eng = Engine(plan, mesh=mesh, data_axis="data", tuning=tuning)
        assert eng.group == 8 and eng.overlap
        assert eng._runner.edge_plan.mode == "boxed"
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 28, 28, 1))
        np.testing.assert_allclose(
            np.asarray(eng.infer(x)), np.asarray(plan(x)),
            rtol=1e-5, atol=1e-5,
        )

    def test_engine_partial_group_padding(self):
        """Requests that don't fill a pipeline group are zero-padded and
        sliced back — results unchanged."""
        from repro.core.dhm.engine import Engine

        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = _compile(topo, params, None, 2)
        mesh = make_pipeline_mesh((2,), ("stage",))
        eng = Engine(plan, microbatch=2, mesh=mesh, n_microbatches=2)
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 28, 28, 1))
        out = eng.infer(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plan(x)), rtol=1e-5, atol=1e-5
        )


class TestMeshSuiteSubprocess:
    """Tier-1 coverage on 1-device machines: re-run this file's mesh tests
    in a subprocess with 8 forced host devices."""

    @pytest.mark.slow
    @pytest.mark.skipif(
        len(jax.devices()) >= 8, reason="mesh tests already ran in-process"
    )
    def test_mesh_suite_subprocess(self):
        repo_root = pathlib.Path(__file__).resolve().parents[1]
        res = subprocess.run(
            [
                sys.executable, "-m", "pytest", "-q", "-x",
                "-k", "not subprocess", str(pathlib.Path(__file__)),
            ],
            capture_output=True,
            text=True,
            env={
                **os.environ,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": str(repo_root / "src"),
            },
            cwd=str(repo_root),
            timeout=1800,
        )
        assert res.returncode == 0, (res.stdout + res.stderr)[-3000:]


class TestPipelineMesh:
    """Single-device checks of the mesh the pipeline accepts."""

    def test_make_pipeline_mesh_axes_are_auto(self):
        from jax.sharding import AxisType

        mesh = make_pipeline_mesh((1, 1), ("stage", "data"))
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)

    def test_explicit_mesh_is_refused(self):
        """``jax.make_mesh`` defaults to Explicit axes, on which the
        final-stage selection raises deep inside JAX; the builder refuses
        such a mesh up front and names the helper."""
        from repro.core.dhm.pipeline import PipelineConfig, build_pipeline

        mesh = jax.make_mesh((1,), ("stage",))
        with pytest.raises(ValueError, match="make_pipeline_mesh"):
            build_pipeline(
                [lambda p, x: x], [{}], mesh=mesh,
                cfg=PipelineConfig(1, 1), io_specs=None,
            )
