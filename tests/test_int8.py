"""True int8 compute: the requantizing integer kernel paths vs the
fake-quant fp32 oracle, dtype-aware fusion widening, the integer pow2 FC
head, and the mixed-bitwidth compiler knob.

The numeric contract under test: with weights baked to int8 codes on the
same dynamic pow2 grid ``fake_quant_dynamic`` uses, and an input already
on its stream grid, every backend's int8 rendering (int8 x int8 -> int32
accumulate -> one exact pow2 dequant -> fp32 epilogue) produces EXACTLY
the fake-quant reference's values — all scales are powers of two, so the
requantization introduces zero extra ULPs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.compiler import QuantSpec, compile_dhm, emit_conv_stage
from repro.core.dhm.fusion import (
    group_working_set,
    plan_elem_bytes,
    widening_budget,
)
from repro.core.quant.fixed_point import (
    FixedPointSpec,
    dynamic_spec,
    fake_quant_dynamic,
    quantize_fixed,
)
from repro.kernels.stream_conv import stream_conv_block, stream_conv_pyramid
from repro.kernels.stream_conv.epilogue import Int8Scales, stream_quant_spec
from repro.kernels.stream_conv.ref import stream_conv_block_ref
from repro.models.cnn import (
    ALL_TOPOLOGIES,
    CHAIN_TOPOLOGIES,
    CNNTopology,
    ConvLayerSpec,
    init_cnn,
)

BITS = 8


def _bake(w, bits=BITS):
    """(int8 codes, Int8Scales-ready w_scale) on the fake_quant grid."""
    spec = dynamic_spec(w, bits)
    codes = quantize_fixed(w, spec).astype(jnp.int8)
    return codes, float(spec.scale)


def _grid_input(key, shape, bits=BITS):
    """A random frame snapped onto the ``bits``-wide stream grid."""
    spec = stream_quant_spec(bits)
    x = jax.random.normal(key, shape)
    return quantize_fixed(x, spec).astype(jnp.float32) * spec.scale


def _case(key, h, w, c, n, k=3):
    kw, kx, kb = jax.random.split(key, 3)
    wts = jax.random.normal(kw, (k, k, c, n)) * 0.5
    b = jax.random.normal(kb, (n,)) * 0.1
    x = _grid_input(kx, (2, h, w, c))
    return x, wts, b


def test_bake_matches_fake_quant_grid():
    """codes * scale == fake_quant_dynamic(w, bits) exactly — the int8
    weight baking and the fake-quant oracle share one grid."""
    w = jax.random.normal(jax.random.PRNGKey(0), (5, 5, 3, 8))
    codes, scale = _bake(w)
    np.testing.assert_array_equal(
        np.asarray(codes, np.float32) * scale,
        np.asarray(fake_quant_dynamic(w, BITS)),
    )


# The stride x pool x rect-frame property grid of the epilogue contract.
GRID = [
    dict(padding="VALID", stride=1, act="relu", pool=2, pool_stride=None),
    dict(padding="VALID", stride=2, act="tanh", pool=0, pool_stride=None),
    dict(padding="SAME", stride=1, act="relu", pool=3, pool_stride=2),
    dict(padding="SAME", stride=2, act="none", pool=2, pool_stride=None),
]


def _oracle(x, wts, b, cfg, bits=BITS):
    """The fake-quant fp32 reference: fake-quantized weights/bias, fp32
    conv, epilogue, stream quant."""
    return stream_conv_block_ref(
        x, fake_quant_dynamic(wts, bits), fake_quant_dynamic(b, bits),
        act_bits=bits, **cfg,
    )


@pytest.mark.parametrize("cfg", GRID, ids=lambda c: (
    f"{c['padding']}-s{c['stride']}-{c['act']}-p{c['pool']}"
))
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_int8_block_matches_fake_quant_oracle(backend, cfg):
    x, wts, b = _case(jax.random.PRNGKey(3), 14, 18, 2, 5)
    codes, w_scale = _bake(wts)
    sc = Int8Scales(in_bits=BITS, w_scale=w_scale)
    got = stream_conv_block(
        x, codes, fake_quant_dynamic(b, BITS),
        act_bits=BITS, int8_scales=sc, backend=backend, **cfg,
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_oracle(x, wts, b, cfg))
    )


@pytest.mark.slow
@pytest.mark.parametrize("cfg", GRID, ids=lambda c: (
    f"{c['padding']}-s{c['stride']}-{c['act']}-p{c['pool']}"
))
def test_int8_block_matches_oracle_interpret(cfg):
    """The interpret backend runs the actual pallas body (int8 patches,
    int32 scratch accumulator, in-kernel requantizing epilogue)."""
    x, wts, b = _case(jax.random.PRNGKey(4), 14, 18, 2, 5)
    codes, w_scale = _bake(wts)
    sc = Int8Scales(in_bits=BITS, w_scale=w_scale)
    got = stream_conv_block(
        x, codes, fake_quant_dynamic(b, BITS),
        act_bits=BITS, int8_scales=sc, backend="pallas_interpret", **cfg,
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_oracle(x, wts, b, cfg))
    )


@pytest.mark.parametrize(
    "backend",
    ["ref", "pallas", pytest.param("pallas_interpret", marks=pytest.mark.slow)],
)
def test_int8_pyramid_matches_fake_quant_oracle(backend):
    """A 2-layer fused group on a rectangular SAME frame: interior layer
    emits int8 codes (1-byte inter-layer slab), last layer emits fp32 —
    exactly the per-layer fake-quant composition."""
    key = jax.random.PRNGKey(5)
    k0, k1, kx = jax.random.split(key, 3)
    layers = (
        ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
        ConvLayerSpec(n_out=5, kernel=3, padding="SAME", pool=2, act="tanh"),
    )
    w0 = jax.random.normal(k0, (3, 3, 2, 4)) * 0.5
    w1 = jax.random.normal(k1, (3, 3, 4, 5)) * 0.5
    b0 = jnp.zeros((4,)) + 0.0625
    b1 = jnp.zeros((5,)) - 0.125
    x = _grid_input(kx, (2, 14, 18, 2))
    (c0, s0), (c1, s1) = _bake(w0), _bake(w1)
    scales = (
        Int8Scales(in_bits=BITS, w_scale=s0),
        Int8Scales(in_bits=BITS, w_scale=s1),
    )
    got = stream_conv_pyramid(
        x, [c0, c1], [fake_quant_dynamic(b0, BITS), fake_quant_dynamic(b1, BITS)],
        layers=layers, act_bits=BITS, int8_scales=scales, backend=backend,
    )
    want = x
    for wts, b, layer in ((w0, b0, layers[0]), (w1, b1, layers[1])):
        want = _oracle(
            want, wts, b,
            dict(padding=layer.padding, stride=layer.stride, act=layer.act,
                 pool=layer.pool, pool_stride=layer.pool_stride),
        )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestCompiledInt8Plans:
    def _topo_params(self, name="lenet5"):
        topo = ALL_TOPOLOGIES[name]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        return topo, params

    @pytest.mark.parametrize(
        "backend",
        ["ref", "pallas",
         pytest.param("pallas_interpret", marks=pytest.mark.slow)],
    )
    def test_plan_logits_match_fake_quant_plan(self, backend):
        """End to end through compile_dhm: the int8 plan's logits equal
        the fake-quant plan's logits exactly for an on-grid frame."""
        topo, params = self._topo_params()
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(1), (2, h, w, topo.input_channels),
            bits=BITS,
        )
        fq = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS), backend=backend,
        )
        i8 = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS,
                            int8_compute=True),
            backend=backend,
        )
        np.testing.assert_array_equal(np.asarray(fq(x)), np.asarray(i8(x)))

    def test_int8_closure_does_not_retrace(self):
        """The int8 jitted closure traces once across repeated batches —
        static Int8Scales must not leak into the pytree."""
        topo, params = self._topo_params()
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS,
                            int8_compute=True),
        )
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(2), (2, h, w, topo.input_channels)
        )
        fwd = plan.jitted_forward()
        fwd(x)
        fwd(x + 0.25)
        fwd(x * 0.5)
        assert fwd._cache_size() == 1

    def test_plan_params_are_int8_codes(self):
        topo, params = self._topo_params()
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS,
                            int8_compute=True),
        )
        assert len(plan.int8_scales) == len(topo.conv_layers)
        for p, sc in zip(plan.conv_params, plan.int8_scales):
            assert p["w"].dtype == jnp.int8
            assert sc.in_bits == BITS
            assert sc.w_scale > 0
        assert plan_elem_bytes(plan.quant) == 1

    def test_stage_quant_kwargs_rebuild_matches(self):
        """Degradation-ladder rebuilds (emit_conv_stage from
        stage_quant_kwargs) reproduce the plan's stage bodies exactly."""
        topo, params = self._topo_params()
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS,
                            int8_compute=True),
        )
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(6), (2, h, w, topo.input_channels)
        )
        st = plan.stages[0]
        rebuilt = emit_conv_stage(
            st.specs, backend=plan.backend, **plan.stage_quant_kwargs(0)
        )
        np.testing.assert_array_equal(
            np.asarray(rebuilt(plan.stage_params(0), x)),
            np.asarray(st.fn(plan.stage_params(0), x)),
        )

    def test_mixed_bitwidth_plan_compiles_and_runs(self):
        topo, params = self._topo_params()
        n = len(topo.conv_layers)
        bits = tuple(6 if i % 2 else 8 for i in range(n))
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(int8_compute=True, per_layer_bits=bits),
        )
        assert plan.quant.mixed_bitwidth
        for i in range(n):
            assert plan.quant.conv_act_bits(i) == bits[i]
        # chain contract: layer i ingests layer i-1's stream width
        for i in range(1, n):
            assert plan.int8_scales[i].in_bits == bits[i - 1]
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(7), (2, h, w, topo.input_channels)
        )
        logits = plan(x)
        assert logits.shape == (2, topo.n_classes)
        assert bool(jnp.isfinite(logits).all())

    def test_int8_requires_bits(self):
        with pytest.raises(ValueError, match="int8_compute requires"):
            QuantSpec(int8_compute=True)
        with pytest.raises(ValueError, match="<= 8"):
            QuantSpec(weight_bits=9, act_bits=9, int8_compute=True)

    def test_per_layer_bits_length_checked(self):
        topo, params = self._topo_params()
        with pytest.raises(ValueError, match="per_layer_bits"):
            compile_dhm(
                topo, params,
                quant=QuantSpec(per_layer_bits=(8,) * 17),
            )


class TestInt8FusionWidening:
    def test_int8_slabs_widen_fusion_groups(self):
        """The tentpole's costing claim, asserted structurally: at the
        probe budget (1 byte under the cheapest whole-run fp32 cost) the
        fp32 plan cannot fuse the full conv stack, the int8 plan can —
        1-byte slabs buy a strictly larger group under the SAME budget."""
        widened = []
        for name, topo in CHAIN_TOPOLOGIES.items():
            idxs = tuple(range(len(topo.conv_layers)))
            probe = widening_budget(topo, idxs)
            if probe is None:
                continue
            if probe["int8_max_group"] > probe["fp32_max_group"]:
                widened.append((name, probe))
        assert widened, "no topology widens under int8 slab costing"

    def test_compiled_plans_realize_the_widening(self):
        """Compile fp32 and int8 plans at the probe budget and compare
        the actual fusion groups the compiler emitted."""
        for name, topo in CHAIN_TOPOLOGIES.items():
            idxs = tuple(range(len(topo.conv_layers)))
            probe = widening_budget(topo, idxs)
            if probe is None or probe["int8_max_group"] <= probe["fp32_max_group"]:
                continue
            params = init_cnn(jax.random.PRNGKey(0), topo)
            fp = compile_dhm(topo, params, vmem_budget=probe["budget"])
            i8 = compile_dhm(
                topo, params,
                quant=QuantSpec(weight_bits=8, act_bits=8,
                                int8_compute=True),
                vmem_budget=probe["budget"],
            )
            fp_max = max(len(g.layers) for g in fp.fusion_groups)
            i8_max = max(len(g.layers) for g in i8.fusion_groups)
            assert i8_max > fp_max, name
            # and the recorded working sets honor the int8 costing
            for g in i8.fusion_groups:
                assert g.working_set == group_working_set(
                    topo, g.layers, block_rows=g.block_rows, elem_bytes=1
                )
            return
        pytest.skip("no widening topology found (covered by the test above)")

    def test_fp32_costing_unchanged(self):
        """elem_bytes=4 defaults reproduce the historical costs — fp32
        plans keep byte-identical working sets."""
        for topo in CHAIN_TOPOLOGIES.values():
            idxs = tuple(range(len(topo.conv_layers)))
            a = group_working_set(topo, idxs, block_rows=8)
            b = group_working_set(topo, idxs, block_rows=8, elem_bytes=4)
            assert a == b


class TestIntPow2Head:
    def test_int_head_matches_fp32_decode_head(self):
        """pow2 packed head: the integer shift-add rendering equals the
        decode-to-fp32 matmul exactly for on-grid activations."""
        topo = CNNTopology(
            name="p2head", input_hw=(12, 12), input_channels=2,
            conv_layers=(
                ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=2,
                              act="tanh"),
            ),
            fc_dims=(16,), n_classes=5,
        )
        params = init_cnn(jax.random.PRNGKey(0), topo)
        x = _grid_input(jax.random.PRNGKey(1), (2, 12, 12, 2))
        fp = compile_dhm(
            topo, params,
            quant=QuantSpec(act_bits=8, pow2_weights=True,
                            per_layer_bits=(8,)),
            backend="ref",
        )
        i8 = compile_dhm(
            topo, params,
            quant=QuantSpec(act_bits=8, pow2_weights=True, int8_compute=True,
                            per_layer_bits=(8,)),
            backend="ref",
        )
        np.testing.assert_array_equal(np.asarray(fp(x)), np.asarray(i8(x)))

    def test_int_head_skips_fp32_matmul(self):
        """The head's jaxpr contains integer dot_generals only — the
        decode-to-fp32 matmul is structurally gone."""
        from repro.analysis.jaxpr_utils import find_primitive

        topo = CNNTopology(
            name="p2head2", input_hw=(12, 12), input_channels=2,
            conv_layers=(
                ConvLayerSpec(n_out=4, kernel=3, padding="SAME", pool=2,
                              act="tanh"),
            ),
            fc_dims=(16,), n_classes=5,
        )
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(act_bits=8, pow2_weights=True, int8_compute=True,
                            per_layer_bits=(8,)),
            backend="ref",
        )
        feat = jax.eval_shape(
            plan.features,
            jax.ShapeDtypeStruct((1, 12, 12, 2), jnp.float32),
        )
        jaxpr = jax.make_jaxpr(plan.head_fn)(
            jnp.zeros(feat.shape, feat.dtype)
        )
        dots = find_primitive(jaxpr, "dot_general")
        assert dots, "head lost its matmuls"
        for eqn in dots:
            for v in eqn.invars:
                assert jnp.issubdtype(v.aval.dtype, jnp.integer), (
                    f"fp32 operand {v.aval.dtype} survived in the packed head"
                )
            assert eqn.outvars[0].aval.dtype == jnp.int32


class TestBitwidthSearchCompilerKnob:
    def test_search_plan_bitwidths_returns_mixed_plan(self):
        from repro.core.quant.bitwidth_search import search_plan_bitwidths

        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(3), (2, h, w, topo.input_channels)
        )

        seen = []

        def evaluate(plan):
            seen.append(plan)
            logits = plan(x)
            # a monotone accuracy proxy: wider streams -> higher "score"
            return float(plan.quant.conv_act_bits(0)) / 10.0

        result, final = search_plan_bitwidths(
            topo, params, evaluate,
            float_accuracy=0.8, bit_range=(4, 6, 8), max_drop=0.25,
            int8_compute=True,
        )
        # every candidate was a REAL compiled int8 plan
        assert len(seen) == 3
        for p in seen:
            assert p.quant.int8_compute
            assert plan_elem_bytes(p.quant) == 1
        # the selected width is a compile-time plan attribute
        assert result.selected_bits == 6  # 0.8 - 0.6 <= 0.25, 0.4 too low
        assert final.quant.per_layer_bits == (6,) * len(topo.conv_layers)
        assert final.quant.int8_compute
        logits = final(x)
        assert logits.shape == (2, topo.n_classes)

    def test_int8_sweep_rejects_wide_bits(self):
        from repro.core.quant.bitwidth_search import search_plan_bitwidths

        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        with pytest.raises(ValueError, match="<= 8"):
            search_plan_bitwidths(
                topo, params, lambda p: 1.0,
                float_accuracy=1.0, bit_range=(12, 16), int8_compute=True,
            )


class TestEngineInt8:
    def test_engine_serves_int8_plan_and_degrades(self):
        """The serving engine's degradation rungs (per_layer, ref) rebuild
        int8 stage bodies through stage_quant_kwargs — logits stay exact
        across rungs."""
        from repro.core.dhm.engine import Engine

        topo = ALL_TOPOLOGIES["lenet5"]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        plan = compile_dhm(
            topo, params,
            quant=QuantSpec(weight_bits=BITS, act_bits=BITS,
                            int8_compute=True),
        )
        h, w = topo.input_shape
        x = _grid_input(
            jax.random.PRNGKey(9), (2, h, w, topo.input_channels)
        )
        want = np.asarray(plan(x))
        eng = Engine(plan, warmup=False)
        fused = eng._ladder[[n for n, _ in eng._ladder].index("fused")][1]()
        per_layer = eng._ladder[
            [n for n, _ in eng._ladder].index("per_layer")
        ][1]()
        ref = eng._ladder[[n for n, _ in eng._ladder].index("ref")][1]()
        np.testing.assert_array_equal(np.asarray(fused(x)), want)
        np.testing.assert_array_equal(np.asarray(per_layer(x)), want)
        np.testing.assert_array_equal(np.asarray(ref(x)), want)


def test_dynamic_spec_matches_fake_quant_scale():
    """dynamic_spec's static pow2 scale reproduces fake_quant_dynamic's
    in-graph scale — including the exact-pow2 max-abs corner."""
    for seed in range(4):
        w = jax.random.normal(jax.random.PRNGKey(seed), (7, 11))
        spec = dynamic_spec(w, 8)
        np.testing.assert_array_equal(
            np.asarray(quantize_fixed(w, spec), np.float32) * spec.scale,
            np.asarray(fake_quant_dynamic(w, 8)),
        )
    # exact power-of-two max abs: the ceil must not tip up an extra bit
    w = jnp.array([0.5, -0.25, 0.125])
    spec = dynamic_spec(w, 6)
    np.testing.assert_array_equal(
        np.asarray(quantize_fixed(w, spec), np.float32) * spec.scale,
        np.asarray(fake_quant_dynamic(w, 6)),
    )


def test_int8_scales_is_static_and_hashable():
    sc = Int8Scales(in_bits=8, w_scale=0.0078125)
    assert hash(sc) == hash(Int8Scales(in_bits=8, w_scale=0.0078125))
    assert isinstance(sc.in_spec, FixedPointSpec)
    assert sc.deq_scale == sc.in_spec.scale * sc.w_scale
