"""Tests for the DHM core: DPN graph expansion (paper Fig. 2 counts),
resource model (Table 2 calibration), throughput model (Table 4),
stage partitioning, and the streaming pipeline executor."""
import dataclasses
import subprocess
import sys

import pytest

from repro.core.dhm import (
    CYCLONE_V_5CGXFC9E7,
    KINTEX7_XC7Z045,
    MultiplierStrategy,
    balance_report,
    cnn_to_dpn,
    dhm_throughput_gops,
    estimate_resources,
    layer_costs_to_dpn,
    partition_stages,
)
from repro.core.dhm.graph import ActorKind
from repro.core.dhm.resources import PAPER_TABLE1
from repro.models.cnn import CIFAR10, LENET5, CNNTopology, ConvLayerSpec


class TestGraph:
    def test_fig2_actor_counts(self):
        """Paper Fig. 2: C=3, N=5, K=3 -> 15 conv engines (135 multipliers,
        15 adder trees), 5 neuron sums (total 20 sums), 5 activations."""
        fig2 = CNNTopology(
            name="fig2",
            input_hw=8,
            input_channels=3,
            conv_layers=(ConvLayerSpec(n_out=5, kernel=3, padding="SAME", pool=0),),
            fc_dims=(),
            n_classes=2,
        )
        g = cnn_to_dpn(fig2, bits=8)
        assert g.count(ActorKind.CONV_ENGINE) == 15
        assert g.total_multipliers() == 135
        assert g.total_adders() == 20  # 15 trees + 5 neuron sums
        assert g.count(ActorKind.ACTIVATION) == 5

    def test_lenet_multiplier_count(self):
        g = cnn_to_dpn(LENET5, bits=5)
        assert g.total_multipliers() == 25500  # 500 + 25000

    def test_validate_catches_duplicates(self):
        g = cnn_to_dpn(LENET5, bits=3)
        g.actors.append(g.actors[-1])
        with pytest.raises(ValueError):
            g.validate()

    def test_layer_costs_dpn(self):
        g = layer_costs_to_dpn("lm", [{"flops": 10.0}] * 4)
        assert g.count(ActorKind.BLOCK) == 4
        assert g.total_flops() == 40.0

    def test_pool_actor_general_window(self):
        """Pool actors model window != stride correctly (cifar10_full:
        3x3/stride-2): output dims follow the VALID sliding rule — NOT
        h_out // window — and the streaming pool buffers (window - 1)
        conv-output lines."""
        from repro.models.cnn import CIFAR10_FULL

        bits = 6
        g = cnn_to_dpn(CIFAR10_FULL, bits=bits)
        # Layer 1: conv out 32x32, 3x3/2 pool -> 15x15.
        p1 = g.actor("pool1_n0")
        assert p1.line_buffer_bits == (3 - 1) * 32 * bits
        assert p1.stream_bytes == 15 * 15 * bits / 8.0
        # Layer 2 consumes the POOLED 15-wide frame: its window actors
        # buffer 15-pixel lines, its engines work on the 15x15 conv out.
        w2 = g.actor("win2_c0")
        assert w2.line_buffer_bits == (5 - 1) * 15 * bits
        e2 = g.actor("conv2_n0_c0")
        assert e2.flops == 2.0 * 5 * 5 * 15 * 15
        # Layer 3: conv out 7x7, pool -> 3x3 (the old h_out // pool rule
        # would have claimed 7 // 3 = 2).
        p3 = g.actor("pool3_n0")
        assert p3.stream_bytes == 3 * 3 * bits / 8.0

    def test_strided_conv_dpn(self):
        """Strided convs shrink the engine payloads (conv output dims
        already reflect the stride) and the window buffers keep the full
        input line width."""
        from repro.models.cnn import CIFAR10_STRIDED

        bits = 6
        g = cnn_to_dpn(CIFAR10_STRIDED, bits=bits)
        e1 = g.actor("conv1_n0_c0")
        assert e1.flops == 2.0 * 5 * 5 * 16 * 16  # 32 -> 16 via stride 2
        w1 = g.actor("win1_c0")
        assert w1.line_buffer_bits == (5 - 1) * 32 * bits  # input lines

    def test_rectangular_frame_dpn(self):
        """(H, W) frames expand without any square assumption: stream
        bytes use H_p * W_p, not H_p**2."""
        from repro.models.cnn import CNNTopology, ConvLayerSpec

        topo = CNNTopology(
            name="rect", input_hw=(12, 20), input_channels=1,
            conv_layers=(
                ConvLayerSpec(n_out=2, kernel=3, padding="SAME", pool=2),
            ),
            fc_dims=(), n_classes=2,
        )
        g = cnn_to_dpn(topo, bits=8)
        p = g.actor("pool1_n0")
        assert p.stream_bytes == (12 // 2) * (20 // 2) * 8 / 8.0


class TestResources:
    def test_table2_dsp_strategy_overflows(self):
        """Paper: DSP-based LeNet5 needs ~72x the device's DSP blocks."""
        g = cnn_to_dpn(LENET5, bits=5)
        rep = estimate_resources(
            g, CYCLONE_V_5CGXFC9E7, bits=5, strategy=MultiplierStrategy.DSP
        )
        assert not rep.fits
        assert 60 < rep.dsp_utilization < 80  # paper: 71.59x

    def test_table2_le_strategy(self):
        """Paper: LE-based needs 433,500 ALMs = 381% of the Cyclone V."""
        g = cnn_to_dpn(LENET5, bits=5)
        rep = estimate_resources(
            g, CYCLONE_V_5CGXFC9E7, bits=5, strategy=MultiplierStrategy.LE
        )
        assert not rep.fits
        assert rep.logic_used == pytest.approx(433_500, rel=0.02)

    def test_table2_le_const_fits(self):
        """Paper: constant-specialized multipliers make LeNet5 FIT on the
        Cyclone V (50,452 ALMs = 44%); our closed-form model lands below
        the device cap (the paper's absolute figure embeds synthesis-tool
        sharing; fractions are Table 1's, measured at 3 bits)."""
        g = cnn_to_dpn(LENET5, bits=5)
        rep = estimate_resources(
            g,
            CYCLONE_V_5CGXFC9E7,
            bits=5,
            strategy=MultiplierStrategy.LE_CONST,
            fractions=PAPER_TABLE1["lenet5"],
        )
        assert rep.fits
        assert rep.logic_utilization < 0.44  # paper's measured upper bound

    def test_specialization_factor(self):
        """Paper: tailored multipliers reduce logic >= 8.6x vs generic LE
        (their 8.6x is a lower bound for us: Table 1's 3-bit fractions have
        more zeros than the unpublished 5-bit ones the paper synthesized)."""
        g = cnn_to_dpn(LENET5, bits=5)
        le = estimate_resources(
            g, CYCLONE_V_5CGXFC9E7, bits=5, strategy=MultiplierStrategy.LE
        )
        const = estimate_resources(
            g,
            CYCLONE_V_5CGXFC9E7,
            bits=5,
            strategy=MultiplierStrategy.LE_CONST,
            fractions=PAPER_TABLE1["lenet5"],
        )
        factor = le.logic_used / const.logic_used
        assert factor >= 8.6  # paper's measured reduction

    def test_table3_all_nets_fit_both_devices(self):
        """Paper Table 3: all three CNNs fit both embedded devices with
        zero DSP blocks and tiny memory."""
        for name, bits in (("lenet5", 3), ("cifar10", 6), ("svhn", 6)):
            topo = {"lenet5": LENET5, "cifar10": CIFAR10, "svhn": CIFAR10}[name]
            g = cnn_to_dpn(topo, bits=bits)
            for dev in (CYCLONE_V_5CGXFC9E7, KINTEX7_XC7Z045):
                rep = estimate_resources(
                    g,
                    dev,
                    bits=bits,
                    strategy=MultiplierStrategy.LE_CONST,
                    fractions=PAPER_TABLE1[name],
                )
                assert rep.fits, rep.summary()
                assert rep.dsp_used == 0  # zero DSP blocks, like the paper
                # memory footprint is line buffers only: ~1% of BRAM
                assert rep.memory_bits < 0.02 * dev.bram_bits

    def test_table3_orderings(self):
        """Qualitative Table 3 claims: logic grows with CNN size, and the
        sparser SVHN (more zeros) uses less logic than Cifar10."""
        reps = {}
        for name, bits in (("lenet5", 3), ("cifar10", 6), ("svhn", 6)):
            topo = {"lenet5": LENET5, "cifar10": CIFAR10, "svhn": CIFAR10}[name]
            g = cnn_to_dpn(topo, bits=bits)
            reps[name] = estimate_resources(
                g,
                CYCLONE_V_5CGXFC9E7,
                bits=bits,
                strategy=MultiplierStrategy.LE_CONST,
                fractions=PAPER_TABLE1[name],
            )
        assert reps["lenet5"].logic_used < reps["svhn"].logic_used
        assert reps["svhn"].logic_used < reps["cifar10"].logic_used


class TestThroughput:
    def test_table4_haddoc2_rows(self):
        """Reproduce the three Haddoc2 rows of Table 4 (<2%)."""
        assert dhm_throughput_gops(LENET5, 65.71).gops == pytest.approx(
            318.48, rel=0.02
        )
        assert dhm_throughput_gops(CIFAR10, 63.89).gops == pytest.approx(
            515.78, rel=0.02
        )
        assert dhm_throughput_gops(CIFAR10, 54.17).gops == pytest.approx(
            437.30, rel=0.02
        )

    def test_fpgaconvnet_speedup(self):
        """Paper: x2.63 over fpgaConvNet on the Cifar10 workload (Zynq)."""
        ours = dhm_throughput_gops(CIFAR10, 54.17).gops
        fpgaconvnet = 166.16
        assert ours / fpgaconvnet == pytest.approx(2.63, rel=0.03)


class TestPartition:
    def test_exact_small(self):
        pa = partition_stages([1, 1, 1, 4, 1, 1, 1], 3)
        assert pa.bottleneck == 4.0
        assert pa.n_stages == 3
        assert pa.boundaries[0] == 0 and pa.boundaries[-1] == 7

    def test_uniform_perfect(self):
        pa = partition_stages([2.0] * 8, 4)
        assert pa.stage_costs == (4.0, 4.0, 4.0, 4.0)

    def test_single_stage(self):
        pa = partition_stages([3, 5, 2], 1)
        assert pa.bottleneck == 10.0

    def test_too_many_stages_raises(self):
        with pytest.raises(ValueError):
            partition_stages([1, 2], 3)

    def test_stage_of_layer_roundtrip(self):
        pa = partition_stages([1, 2, 3, 4, 5, 6], 3)
        for layer in range(6):
            s = pa.stage_of_layer(layer)
            assert layer in pa.layers_of_stage(s)

    @pytest.mark.parametrize("seed", range(40))
    def test_property_optimal_vs_greedy(self, seed):
        """DP bottleneck always >= max(cost) and >= total/S (lower bounds),
        over seeded random cost vectors and stage counts."""
        import random

        rnd = random.Random(seed)
        n = rnd.randint(2, 30)
        s = min(rnd.randint(1, 6), n)
        costs = [rnd.uniform(0.1, 10.0) for _ in range(n)]
        pa = partition_stages(costs, s)
        assert pa.bottleneck >= max(costs) - 1e-9
        assert pa.bottleneck >= sum(costs) / s - 1e-9
        assert sum(pa.stage_costs) == pytest.approx(sum(costs))

    def test_balance_report(self):
        br = balance_report([1.0] * 8, 4, 16)
        assert br.bubble_fraction == pytest.approx(3 / 19)
        assert br.imbalance == pytest.approx(1.0)


class TestConvStage:
    def test_make_conv_stage_matches_unfused(self):
        """The fused conv stage body == the unfused reference composition,
        and is shape-homogeneous (SAME, pool=0, C == N)."""
        import jax
        import jax.numpy as jnp

        from repro.core.dhm.pipeline import make_conv_stage
        from repro.kernels.stream_conv import stream_conv_block_ref

        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        params = {
            "w": jax.random.normal(k1, (3, 3, 4, 4)) * 0.3,
            "b": jnp.zeros((4,)),
        }
        x = jax.random.normal(k2, (2, 8, 8, 4))
        stage_fn = make_conv_stage(padding="SAME", act="tanh", pool=0)
        y = stage_fn(params, x)
        assert y.shape == x.shape
        ref = stream_conv_block_ref(
            x, params["w"], params["b"], padding="SAME", act="tanh", pool=0
        )
        import numpy as np

        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


PIPELINE_SUBPROCESS = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.dhm.pipeline import (
    PipelineConfig, make_pipeline_mesh, pipeline_forward, stack_stage_params,
)
mesh = make_pipeline_mesh((4,), ('stage',))
Ws = [jax.random.normal(jax.random.PRNGKey(i), (8, 8)) * 0.3 for i in range(4)]
params = stack_stage_params([{'w': w} for w in Ws])
mbs = jax.random.normal(jax.random.PRNGKey(9), (6, 2, 8))
def stage_fn(p, x):
    return jnp.tanh(x @ p['w'])
out = pipeline_forward(stage_fn, params, mbs, mesh=mesh, cfg=PipelineConfig(4, 6))
ref = mbs
for w in Ws:
    ref = jnp.tanh(ref @ w)
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), 'pipeline mismatch'
print('OK')
"""


class TestPipeline:
    @pytest.mark.slow
    def test_pipeline_matches_sequential_4dev(self):
        """Streaming shard_map pipeline == sequential layer application
        (run in a subprocess with 4 forced host devices)."""
        import os
        import pathlib

        repo_root = pathlib.Path(__file__).resolve().parents[1]
        res = subprocess.run(
            [sys.executable, "-c", PIPELINE_SUBPROCESS],
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
