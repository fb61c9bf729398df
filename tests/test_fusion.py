"""Tests for cross-layer VMEM-resident fusion: the budget-aware planner
(maximal groups, per-layer fallback, exact-fit boundaries), composed-halo
correctness of the fused pyramid kernel vs the hand-composed reference on
every topology and backend, and the structural one-pallas_call-per-group
guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.compiler import QuantSpec, compile_dhm
from repro.core.dhm.fusion import (
    DEFAULT_VMEM_BUDGET,
    group_working_set,
    plan_fusion_groups,
)
from repro.kernels.stream_conv import stream_conv_pyramid
from repro.models.cnn import (
    ALL_TOPOLOGIES,
    CHAIN_TOPOLOGIES,
    CNNTopology,
    ConvLayerSpec,
    PAPER_TOPOLOGIES,
    cnn_apply_reference,
    init_cnn,
)


# The ONE jaxpr-walking helper, shared with the static-analysis engine
# (tests and the `repro.analysis` CLI can never drift apart).
from repro.analysis.jaxpr_utils import count_primitive as _count_primitive


def _mk_inputs(topo, seed=4, batch=2):
    params = init_cnn(jax.random.PRNGKey(seed - 1), topo)
    h, w = topo.input_shape
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (batch, h, w, topo.input_channels)
    )
    return params, x


# A small two-layer topology whose working sets are a few tens of KB —
# cheap enough for interpret-mode oracle runs in the fast tier, gnarly
# enough to exercise SAME padding, overlapping pool and rectangularity.
SMALL2 = CNNTopology(
    name="small2", input_hw=(14, 18), input_channels=2,
    conv_layers=(
        ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
        ConvLayerSpec(n_out=5, kernel=3, padding="SAME", pool=2, act="tanh"),
    ),
    fc_dims=(8,), n_classes=3,
)


class TestPlanner:
    def test_paper_topologies_fuse_whole_pyramid_by_default(self):
        """Under the default VMEM budget every paper topology's feature
        extractor is ONE fusion group (single fused kernel + FC head)."""
        for name, topo in PAPER_TOPOLOGIES.items():
            params, _ = _mk_inputs(topo)
            plan = compile_dhm(topo, params)
            groups = plan.fusion_groups
            assert len(groups) == 1, (name, groups)
            assert groups[0].layers == tuple(range(len(topo.conv_layers)))
            assert groups[0].working_set <= DEFAULT_VMEM_BUDGET

    def test_tiny_budget_gives_per_layer_plan(self):
        """A budget too small for any 2-layer group degenerates to the
        pre-fusion plan: all-singleton groups, same structure and logits
        as fusion disabled."""
        topo = PAPER_TOPOLOGIES["cifar10"]
        params, x = _mk_inputs(topo)
        tiny = compile_dhm(topo, params, vmem_budget=1024)
        assert [g.layers for g in tiny.fusion_groups] == [(0,), (1,), (2,)]
        off = compile_dhm(topo, params, vmem_budget=0)
        np.testing.assert_array_equal(
            np.asarray(tiny(x)), np.asarray(off(x))
        )

    def test_budget_exactly_fits_is_inclusive(self):
        """The planner accepts a group whose costed working set equals the
        budget exactly, and rejects it one byte under."""
        topo = SMALL2
        ws = group_working_set(topo, (0, 1))  # whole-frame block
        groups = plan_fusion_groups(topo, (0, 1), vmem_budget=ws)
        assert [g.layers for g in groups] == [(0, 1)]
        assert groups[0].working_set == ws
        # One byte below: the whole-frame block no longer fits; the
        # planner either row-blocks (smaller working set) or splits.
        groups = plan_fusion_groups(topo, (0, 1), vmem_budget=ws - 1)
        if len(groups) == 1:
            assert groups[0].working_set <= ws - 1
            assert groups[0].block_rows >= 1
        else:
            assert [g.layers for g in groups] == [(0,), (1,)]

    def test_huge_budget_whole_pyramid(self):
        for topo in CHAIN_TOPOLOGIES.values():
            groups = plan_fusion_groups(
                topo, range(len(topo.conv_layers)), vmem_budget=2**40
            )
            assert len(groups) == 1
            assert groups[0].layers == tuple(range(len(topo.conv_layers)))

    def test_mid_budget_splits_into_maximal_groups(self):
        """A budget that fits 2-layer but not 3-layer groups on cifar10
        yields a maximal first group and a trailing singleton."""
        topo = PAPER_TOPOLOGIES["cifar10"]
        ws3 = group_working_set(topo, (0, 1, 2), block_rows=1)
        ws2 = group_working_set(topo, (0, 1), block_rows=1)
        assert ws2 < ws3
        groups = plan_fusion_groups(topo, (0, 1, 2), vmem_budget=ws2)
        assert [g.layers for g in groups] == [(0, 1), (2,)]

    def test_budget_shrinks_block_rows(self):
        """Between whole-frame and nothing, the planner keeps the group
        and streams smaller row blocks."""
        topo = SMALL2
        whole = group_working_set(topo, (0, 1))
        one_row = group_working_set(topo, (0, 1), block_rows=1)
        assert one_row < whole
        groups = plan_fusion_groups(topo, (0, 1), vmem_budget=whole - 1)
        if len(groups) == 1:  # fits at a reduced block size
            assert 1 <= groups[0].block_rows
            assert groups[0].working_set < whole

    def test_noncontiguous_layers_raise(self):
        with pytest.raises(ValueError, match="contiguous"):
            plan_fusion_groups(PAPER_TOPOLOGIES["cifar10"], (0, 2))

    def test_negative_budget_raises(self):
        with pytest.raises(ValueError, match="vmem_budget"):
            compile_dhm(
                PAPER_TOPOLOGIES["cifar10"],
                _mk_inputs(PAPER_TOPOLOGIES["cifar10"])[0],
                vmem_budget=-1,
            )


class TestFusedCorrectness:
    """Composed-halo correctness: fused plans match the hand-composed
    reference on every topology, fp32 and quantized."""

    @pytest.mark.parametrize("name", sorted(CHAIN_TOPOLOGIES))
    def test_fused_plan_matches_reference_compiled(self, name):
        topo = ALL_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params, backend="pallas")
        assert any(g.fused for g in plan.fusion_groups)
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("name", sorted(CHAIN_TOPOLOGIES))
    def test_fused_quant_plan_matches_reference(self, name):
        bits = {"lenet5": 3}.get(name, 6)
        topo = ALL_TOPOLOGIES[name]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(
            topo, params, quant=QuantSpec(weight_bits=bits, act_bits=bits),
            backend="pallas",
        )
        ref = cnn_apply_reference(
            params, topo, x, weight_bits=bits, act_bits=bits
        )
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_fused_oracle_small_topology(self):
        """The exact multi-layer kernel program (interpreter) on the small
        gnarly topology: overlapping pool + SAME composed halos."""
        params, x = _mk_inputs(SMALL2, batch=1)
        plan = compile_dhm(SMALL2, params, backend="pallas_interpret")
        assert [g.layers for g in plan.fusion_groups] == [(0, 1)]
        ref = cnn_apply_reference(params, SMALL2, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-5
        )

    def test_row_blocking_does_not_change_values(self):
        """Streaming the pyramid in small row blocks (composed halo per
        block) is bit-identical to the whole-frame block, through the
        kernel oracle."""
        params, x = _mk_inputs(SMALL2, batch=1)
        ws = [p["w"] for p in params["conv"]]
        bs = [p["b"] for p in params["conv"]]
        whole = stream_conv_pyramid(
            x, ws, bs, layers=SMALL2.conv_layers,
            backend="pallas_interpret", block_rows=0,
        )
        for br in (1, 2):
            blocked = stream_conv_pyramid(
                x, ws, bs, layers=SMALL2.conv_layers,
                backend="pallas_interpret", block_rows=br,
            )
            np.testing.assert_array_equal(
                np.asarray(whole), np.asarray(blocked)
            )

    @pytest.mark.slow
    @pytest.mark.parametrize("name", ["lenet5", "cifar10_full"])
    def test_fused_oracle_matches_reference(self, name):
        """Interpreter oracle on the real topologies, including
        cifar10_full's overlapping 3x3/stride-2 pool through the composed
        halo."""
        topo = ALL_TOPOLOGIES[name]
        params, x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(topo, params, backend="pallas_interpret")
        assert any(g.fused for g in plan.fusion_groups)
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-5
        )

    def test_ref_backend_fused_plan_matches_reference(self):
        """Fusion is a scheduling decision on the ref backend too (the
        group lowers as the per-layer chain)."""
        topo = PAPER_TOPOLOGIES["cifar10"]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params, backend="ref")
        ref = cnn_apply_reference(params, topo, x)
        np.testing.assert_allclose(
            np.asarray(plan(x)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


class TestStructure:
    def test_one_pallas_call_per_fusion_group(self):
        """Structural: a fused plan traces to exactly ONE pallas_call per
        fusion group — the whole feature extractor of a paper topology is
        a single kernel invocation. Enforced through the static-analysis
        registry (invariant V002), so this test and the CLI gate can
        never drift apart."""
        from repro.analysis.verify import verify_plan

        topo = PAPER_TOPOLOGIES["cifar10"]
        params, _x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(topo, params, backend="pallas_interpret")
        assert verify_plan(plan, ids=("V002",)) == []
        assert len(plan.fusion_groups) == 1
        # and the per-layer plan keeps one pallas_call per (single-layer
        # group ==) layer
        plan_pl = compile_dhm(
            topo, params, backend="pallas_interpret", vmem_budget=0
        )
        assert verify_plan(plan_pl, ids=("V002",)) == []
        assert len(plan_pl.fusion_groups) == len(topo.conv_layers)

    def test_one_matmul_per_layer_inside_group(self):
        """The fused pyramid keeps the one-MXU-matmul-per-layer contract:
        a fused 3-layer group traces to exactly 3 dot_generals and no
        lax.conv (registry invariants V001/V003)."""
        from repro.analysis.verify import verify_plan

        topo = PAPER_TOPOLOGIES["cifar10"]
        params, _x = _mk_inputs(topo, batch=1)
        plan = compile_dhm(topo, params, backend="pallas_interpret")
        assert verify_plan(plan, ids=("V001", "V003")) == []

    def test_boundary_stream_bytes_reports_pooled_frame(self):
        """The DPN boundary-stream payload (what fusion keeps on-chip per
        fused layer edge) is the pooled output frame at the stream
        bit-width: cifar10 conv1 = 32 maps x 16x16 pooled pixels x 6b."""
        topo = PAPER_TOPOLOGIES["cifar10"]
        params, _ = _mk_inputs(topo)
        plan = compile_dhm(
            topo, params, quant=QuantSpec(weight_bits=6, act_bits=6)
        )
        expected = 32 * 16 * 16 * 6 / 8
        assert plan.graph.boundary_stream_bytes(1) == pytest.approx(expected)

    def test_call_reuses_one_jitted_closure(self):
        """CompiledDHM.__call__ runs one cached end-to-end jitted closure:
        repeated calls never retrace, and the donated variant is a
        separate cached entry."""
        topo = PAPER_TOPOLOGIES["lenet5"]
        params, x = _mk_inputs(topo)
        plan = compile_dhm(topo, params)
        first = plan.jitted_forward()
        for _ in range(4):
            plan(x)
        assert plan.jitted_forward() is first
        assert first._cache_size() == 1
        donating = plan.jitted_forward(donate=True)
        assert donating is not first
        x2 = jnp.array(x)
        out = donating(x2)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plan(x)), rtol=1e-6, atol=1e-6
        )
