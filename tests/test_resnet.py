"""Residual nets through the DHM compiler: batch norm folded at compile
time, residual joins inside the fused pyramid kernel, the padded max-pool
and global average pooling, all against the hand-composed oracle
``cnn_apply_reference`` on seeded random weights, at a small size.

The small net has ResNet's shape: a 7x7/2 stem with the 3x3/2 pool
padded by 1, one block with an identity shortcut and one with a 1x1/2
projection, global average pooling and one dense layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dhm.compiler import (
    PlanCheckError,
    QuantSpec,
    _cached_layout,
    check_plan,
    compile_dhm,
    emit_conv_stage,
    validate_topology,
)
from repro.core.dhm.engine import Engine
from repro.core.dhm.fusion import (
    FusionGroup,
    layer_units,
    plan_fusion_groups,
)
from repro.core.dhm.graph import cnn_to_dpn, conv_layer_flops
from repro.core.quant.fixed_point import dynamic_spec, quantize_fixed
from repro.kernels.stream_conv import stream_conv_block, stream_conv_pyramid
from repro.kernels.stream_conv.epilogue import Int8Scales, stream_quant_spec
from repro.models.cnn import (
    ALL_TOPOLOGIES,
    CNNTopology,
    ConvLayerSpec,
    RESNET18,
    cnn_apply_reference,
    fold_bn,
    init_cnn,
    resnet_layers,
)

TINY = CNNTopology(
    name="tiny_resnet", input_hw=32, input_channels=3,
    conv_layers=resnet_layers((8, 16), blocks_per_stage=1), fc_dims=(),
    n_classes=10, global_pool=True,
)
INT8 = QuantSpec(weight_bits=8, act_bits=8, int8_compute=True)
PALLAS = ("ref", "pallas", "pallas_interpret")


def _random_bn(p, key):
    """The layer's batch norm with random statistics (the identity BN of
    ``init_cnn`` would make folding vacuous)."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    n = p["b"].shape[0]
    p = dict(p)
    p.update(
        gamma=jax.random.uniform(k1, (n,), minval=0.5, maxval=1.5),
        beta=0.1 * jax.random.normal(k2, (n,)),
        mean=0.1 * jax.random.normal(k3, (n,)),
        var=jax.random.uniform(k4, (n,), minval=0.5, maxval=1.5),
    )
    if "proj" in p:
        p["proj"] = _random_bn(p["proj"], k5)
    return p


def _params(topo=TINY, seed=0):
    key = jax.random.PRNGKey(seed)
    params = init_cnn(key, topo)
    params["conv"] = [
        _random_bn(p, jax.random.fold_in(key, i))
        for i, p in enumerate(params["conv"])
    ]
    return params


def _grid_frames(shape, seed=1, bits=8):
    """Frames on the ``bits`` stream grid, as the int8 plan ingests them."""
    step = stream_quant_spec(bits).scale
    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    return jnp.clip(jnp.round(x / step), -(2 ** (bits - 1)), 2 ** (bits - 1) - 1) * step


# ---------------------------------------------------------------------------
# topology


def test_resnet18_workload_counts_projections():
    """He et al. Table 1's 18-layer net: 1,813,561,344 conv multiply-adds
    a frame with the three 1x1/2 projections, 11.7 M parameters."""
    assert RESNET18.feature_extractor_macs() == 1_813_561_344
    assert RESNET18.feature_extractor_ops() + 2 * 512 * 1000 == 3_628_146_688
    assert sorted(RESNET18.projection_shapes()) == [6, 10, 14]
    assert RESNET18.feature_shape() == (7, 7, 512)
    assert RESNET18.head_in_dim() == 512
    shapes = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), RESNET18))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert 11.6e6 < n < 11.8e6
    assert ALL_TOPOLOGIES["resnet18"] is RESNET18


@pytest.mark.parametrize(
    "layers, match",
    [
        ((ConvLayerSpec(4, 3, "SAME", pool=0, join="identity"),), "before layer 0"),
        (
            (ConvLayerSpec(4, 3, "SAME", pool=0),
             ConvLayerSpec(8, 3, "SAME", pool=0, join="identity")),
            "use a project join",
        ),
        (
            (ConvLayerSpec(3, 3, "SAME", pool=2),
             ConvLayerSpec(3, 3, "SAME", pool=0, join="identity")),
            "pools",
        ),
        ((ConvLayerSpec(4, 3, "SAME", pool=3, pool_pad=2),), "pool_pad"),
        ((ConvLayerSpec(4, 3, "SAME", pool=0, join="skip"),), "unknown join"),
    ],
)
def test_validate_refuses_bad_blocks(layers, match):
    topo = CNNTopology("bad", 16, 3, layers, (), 10, global_pool=True)
    with pytest.raises(ValueError, match=match):
        validate_topology(topo)


# ---------------------------------------------------------------------------
# the plan against the oracle


@pytest.mark.parametrize("backend", PALLAS)
@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_plan_matches_reference(backend, quant):
    """The compiled plan (BN folded, joins in the pyramid kernel, padded
    pool in the stem's kernel, pooled head) against the unfused oracle:
    float to rounding, 8-bit on-grid frames exactly."""
    params = _params()
    x = _grid_frames((2, 32, 32, 3))
    if quant == "fp32":
        plan = compile_dhm(TINY, params, backend=backend)
        want = cnn_apply_reference(params, TINY, x)
        np.testing.assert_allclose(plan(x), want, rtol=0, atol=1e-5)
    else:
        plan = compile_dhm(TINY, params, quant=INT8, backend=backend)
        want = cnn_apply_reference(params, TINY, x, weight_bits=8, act_bits=8)
        np.testing.assert_array_equal(plan(x), want)
    assert [g.layers for g in plan.fusion_groups] == [(0,), (1, 2, 3, 4)]


@pytest.mark.parametrize("quant", [QuantSpec(), INT8], ids=["fp32", "int8"])
def test_pooled_head_asks_for_full_float32(quant):
    """The globally pooled features lie on no grid, so the MXU's default
    one-pass bf16 would round them and move every logit by about 2^-9 of
    its size: the pooled head's dot asks for HIGHEST precision. (The CPU
    computes float32 products either way, so only the request can be
    checked here.)"""
    plan = compile_dhm(TINY, _params(), quant=quant, backend="ref")
    feats = jax.ShapeDtypeStruct((2, 2, 2, TINY.conv_layers[-1].n_out), jnp.float32)
    dots = [
        e for e in jax.make_jaxpr(plan.head_fn)(feats).eqns
        if e.primitive.name == "dot_general"
    ]
    assert dots
    for e in dots:
        assert e.params["precision"] == (
            jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST
        )


def test_bn_folding_matches_unfolded_reference():
    """Folding BN into each kernel and bias (projections too) is exact
    math: the folded conv agrees with conv-then-BN within 1e-5."""
    params = _params()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3))
    folded = {**params, "conv": [fold_bn(p) for p in params["conv"]]}
    assert all("gamma" not in p for p in folded["conv"])
    assert "gamma" not in folded["conv"][4]["proj"]
    want = cnn_apply_reference(params, TINY, x)
    got = cnn_apply_reference(folded, TINY, x)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    plan = compile_dhm(TINY, params, backend="ref")
    np.testing.assert_allclose(plan(x), want, rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# kernels


def _block_specs(join):
    stride = 2 if join == "project" else 1
    n = 16 if join == "project" else 8
    return (
        ConvLayerSpec(n, 3, "SAME", pool=0, act="relu", stride=stride),
        ConvLayerSpec(n, 3, "SAME", pool=0, act="relu", join=join),
    )


def _lax_block(x, ws, bs, proj, join):
    """The basic block as separate lax ops: conv, bias, ReLU, conv, bias,
    shortcut added, ReLU."""

    def conv(h, w, s, padding="SAME"):
        return jax.lax.conv_general_dilated(
            h, w.astype(jnp.float32), (s, s), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
        )

    s = 2 if join == "project" else 1
    h = jnp.maximum(conv(x, ws[0], s) + bs[0], 0)
    sc = x if join == "identity" else conv(x, proj[0], s, "VALID") + proj[1]
    return jnp.maximum(conv(h, ws[1], 1) + bs[1] + sc, 0)


@pytest.mark.parametrize("block_rows", [1, 2, 3])
@pytest.mark.parametrize("join", ["identity", "project"])
def test_join_kernel_matches_lax_float(join, block_rows):
    """The pyramid kernel's join against the lax composition, with the
    block's output streamed in several row blocks (7 rows: blocks of 1,
    2 and 3 leave a partial last block)."""
    specs = _block_specs(join)
    c, n = 8, specs[1].n_out
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (2, 14 if join == "project" else 7, 9, c))
    ws = [0.2 * jax.random.normal(ks[1], (3, 3, c, n)),
          0.2 * jax.random.normal(ks[2], (3, 3, n, n))]
    bs = [0.1 * jax.random.normal(ks[3], (n,)), 0.1 * jax.random.normal(ks[4], (n,))]
    proj = None
    if join == "project":
        proj = (0.3 * jax.random.normal(ks[5], (1, 1, c, n)), 0.1 * jnp.ones((n,)))
    want = _lax_block(x, ws, bs, proj, join)
    for backend in ("pallas_interpret", "pallas"):
        got = stream_conv_pyramid(
            x, ws, bs, layers=specs, block_rows=block_rows, backend=backend,
            projections=[None, proj], first_layer=0,
        )
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("block_rows", [2, 3])
@pytest.mark.parametrize("join", ["identity", "project"])
def test_join_kernel_matches_lax_int8(join, block_rows):
    """The integer rendering: int8 codes, int32 accumulation, the
    shortcut dequantized (or projected on the integer MXU under its own
    Int8Scales) and added before the ReLU, then requantized. On-grid
    inputs and pow2 weight grids make it exact against lax on the
    dequantized values."""
    bits = 8
    specs = _block_specs(join)
    c, n = 8, specs[1].n_out
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = _grid_frames((2, 14 if join == "project" else 7, 9, c), seed=7)

    def bake(w):
        spec = dynamic_spec(w, bits)
        return quantize_fixed(w, spec).astype(jnp.int8), float(spec.scale)

    ws, scales = zip(*[
        bake(0.2 * jax.random.normal(ks[1], (3, 3, c, n))),
        bake(0.2 * jax.random.normal(ks[2], (3, 3, n, n))),
    ])
    bs = [jnp.full((n,), 2.0 ** -5), jnp.full((n,), -(2.0 ** -6))]
    proj, proj_sc, proj_f = None, None, None
    if join == "project":
        pw, pscale = bake(0.3 * jax.random.normal(ks[5], (1, 1, c, n)))
        proj = (pw, jnp.full((n,), 2.0 ** -4))
        proj_sc = Int8Scales(in_bits=bits, w_scale=pscale)
        proj_f = (pw.astype(jnp.float32) * pscale, proj[1])
    int8_scales = (
        Int8Scales(in_bits=bits, w_scale=scales[0]),
        Int8Scales(in_bits=bits, w_scale=scales[1], proj=proj_sc),
    )
    step = stream_quant_spec(bits).scale

    def q(v):
        return jnp.clip(jnp.round(v / step), -128, 127) * step

    wf = [w.astype(jnp.float32) * s for w, s in zip(ws, scales)]
    s = 2 if join == "project" else 1
    conv = lambda h, w, st, pad="SAME": jax.lax.conv_general_dilated(  # noqa: E731
        h, w, (st, st), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )
    h = q(jnp.maximum(conv(x, wf[0], s) + bs[0], 0))
    sc = x if join == "identity" else conv(x, proj_f[0], s, "VALID") + proj_f[1]
    want = q(jnp.maximum(conv(h, wf[1], 1) + bs[1] + sc, 0))
    for backend in ("pallas_interpret", "pallas", "ref"):
        got = stream_conv_pyramid(
            x, ws, bs, layers=specs, act_bits=bits, int8_scales=int8_scales,
            block_rows=block_rows, backend=backend, projections=[None, proj],
            first_layer=0,
        )
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", PALLAS)
def test_join_kernel_is_named_after_its_layers(backend):
    """A group with a join runs under the name that gives its plan layers,
    which the residual metrics read from the device trace; without the
    index of its first layer it is refused on every backend, so no
    residual kernel can run under a name those metrics cannot read."""
    specs = _block_specs("identity")
    x = jnp.zeros((1, 7, 9, 8))
    ws = [jnp.zeros((3, 3, 8, 8))] * 2
    bs = [jnp.zeros((8,))] * 2
    with pytest.raises(ValueError, match="first_layer"):
        stream_conv_pyramid(x, ws, bs, layers=specs, backend=backend)
    text = jax.jit(
        lambda a: stream_conv_pyramid(
            a, ws, bs, layers=specs, backend="pallas_interpret", first_layer=3,
        )
    ).lower(x).as_text()
    assert "stream_conv_residual_pallas_l3_4" in text


@pytest.mark.parametrize("hw, k, stride, block_r", [
    ((18, 18), 3, 1, 2), ((17, 13), 3, 1, 4), ((32, 32), 7, 2, 8), ((21, 26), 7, 2, 4),
])
@pytest.mark.parametrize("backend", ["pallas_interpret", "pallas", "ref"])
def test_padded_maxpool_matches_reduce_window(hw, k, stride, block_r, backend):
    """The 3x3/2 max-pool padded by 1 (ResNet's stem) against
    ``reduce_window`` with -inf padding, on odd and even maps, in several
    row blocks, with the stem's 7x7/2 conv on 3 channels (which lowers
    through the space-to-depth fold)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(ks[0], (2, *hw, 3))
    w = 0.3 * jax.random.normal(ks[1], (k, k, 3, 8))
    b = 0.1 * jax.random.normal(ks[2], (8,))
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    ) + b
    # Pool before the ReLU on purpose: -inf padding is then visible
    # (zero padding would differ where a window's real values are all
    # negative).
    want = jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )
    got = stream_conv_block(
        x, w, b, padding="SAME", stride=stride, act="none", pool=3,
        pool_stride=2, pool_pad=1, backend=backend, block_r=block_r,
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# planning


def test_closed_form_costs_equal_the_actor_graph():
    """``conv_layer_flops`` is the per-layer sum of ``cnn_to_dpn``'s actor
    payloads, on the five chain topologies and on a residual one (join
    adds, projection engines and sums, padded pool)."""
    for topo in [ALL_TOPOLOGIES[n] for n in (
        "lenet5", "cifar10", "svhn", "cifar10_full", "cifar10_strided"
    )] + [TINY]:
        graph = cnn_to_dpn(topo, bits=8)
        by_layer = {}
        for a in graph.actors:
            by_layer[a.layer] = by_layer.get(a.layer, 0.0) + a.flops
        want = [by_layer[i + 1] for i in range(len(topo.conv_layers))]
        assert conv_layer_flops(topo) == pytest.approx(want, rel=0, abs=0)
    graph = cnn_to_dpn(TINY, bits=8)
    assert graph.count(graph.actors[0].kind.__class__("join")) == 8 + 16
    assert graph.total_multipliers() == TINY.n_multipliers()


def test_compile_builds_no_actor_graph(monkeypatch):
    """The compile path costs layers in closed form; the actor graph is
    built only when ``plan.graph`` is read."""
    import repro.core.dhm.compiler as compiler

    def refuse(*a, **k):
        raise AssertionError("compile_dhm built the actor graph")

    monkeypatch.setattr(compiler, "cnn_to_dpn", refuse)
    compiler._cached_dpn.cache_clear()
    plan = compile_dhm(TINY, _params(), quant=INT8, n_stages=2)
    assert plan.n_stages == 2
    monkeypatch.undo()
    assert plan.graph.total_multipliers() == TINY.n_multipliers()


@pytest.mark.parametrize("budget", [None, 2**23, 12 * 2**20, 2**26])
def test_planner_never_cuts_a_block(budget):
    """At any budget, every ResNet-18 fusion group holds whole blocks."""
    idxs = tuple(range(len(RESNET18.conv_layers)))
    groups = plan_fusion_groups(RESNET18, idxs, vmem_budget=budget, elem_bytes=1)
    assert [li for g in groups for li in g.layers] == list(idxs)
    for g in groups:
        layer_units(RESNET18.conv_layers, g.layers)  # raises on a cut
    assert groups[0].layers == (0,)  # the padded-pool stem stays alone


def test_block_too_large_for_the_budget_raises():
    with pytest.raises(ValueError, match="basic block of layers 1..2"):
        plan_fusion_groups(RESNET18, range(17), vmem_budget=2**16, elem_bytes=1)
    with pytest.raises(ValueError, match="basic block"):
        compile_dhm(TINY, _params(), vmem_budget=0)


@pytest.mark.parametrize("topo", [TINY, RESNET18], ids=["tiny", "resnet18"])
def test_two_stages_split_between_blocks(topo):
    units = layer_units(topo.conv_layers, range(len(topo.conv_layers)))
    starts = {u[0] for u in units}
    layout = _cached_layout(topo, 2)
    assert layout.n_stages == 2
    assert layout.boundaries[1] in starts
    with pytest.raises(ValueError, match="n_stages"):
        compile_dhm(TINY, _params(), n_stages=4)


def test_minimal_groups_keep_blocks_whole():
    """``emit_conv_stage`` without groups (the Engine's per-layer rung)
    runs each block as one group and still matches the plan."""
    params = _params()
    plan = compile_dhm(TINY, params, quant=INT8, backend="pallas")
    st = plan.stages[0]
    fn = emit_conv_stage(
        st.specs, backend="pallas", **plan.stage_quant_kwargs(0)
    )
    x = _grid_frames((2, 32, 32, 3))
    np.testing.assert_array_equal(
        plan.head_fn(fn(plan.stage_params(0), x)), plan(x)
    )


# ---------------------------------------------------------------------------
# the plan's self-check and serving


def _cut_plan(plan):
    """The plan with its residual group split inside the first block."""
    st = plan.stages[0]
    groups = (
        st.groups[0],
        FusionGroup(layers=(1,), block_rows=0, working_set=0),
        FusionGroup(layers=(2, 3, 4), block_rows=0, working_set=0),
    )
    return dataclasses.replace(
        plan, stages=(dataclasses.replace(st, groups=groups),)
    )


def test_check_plan_passes_and_refuses_a_cut_block():
    plan = compile_dhm(TINY, _params(), quant=INT8, backend="pallas")
    check_plan(plan)
    with pytest.raises(PlanCheckError) as ei:
        check_plan(_cut_plan(plan))
    assert "V306" in ei.value.invariants


def test_engine_serves_a_residual_plan_and_refuses_a_cut_one():
    params = _params()
    plan = compile_dhm(TINY, params, quant=INT8, backend="pallas")
    x = _grid_frames((4, 32, 32, 3))
    eng = Engine(plan, microbatch=4, auto_flush=False)
    try:
        assert eng.rung == "fused"
        futs = [eng.submit(np.asarray(x[i])) for i in range(4)]
        eng.flush()
        got = np.concatenate([np.asarray(f.result(timeout=60)) for f in futs])
    finally:
        eng.stop()
    np.testing.assert_array_equal(
        got, cnn_apply_reference(params, TINY, x, weight_bits=8, act_bits=8)
    )
    with pytest.raises(PlanCheckError):
        Engine(_cut_plan(plan), microbatch=4, auto_flush=False)


@pytest.fixture(scope="module")
def resnet18_plan():
    return compile_dhm(RESNET18, init_cnn(jax.random.PRNGKey(0), RESNET18), quant=INT8)


def test_resnet18_plan(resnet18_plan):
    """ResNet-18 compiles through ``compile_dhm`` into the stem's kernel
    and residual groups of whole blocks, and passes the plan check."""
    plan = resnet18_plan
    groups = plan.fusion_groups
    assert groups[0].layers == (0,)
    assert all(
        len(layer_units(RESNET18.conv_layers, g.layers)) >= 1 for g in groups
    )
    assert [li for g in groups for li in g.layers] == list(range(17))
    assert set(plan.conv_params[6]) == {"w", "b", "proj"}
    assert plan.conv_params[6]["proj"]["w"].dtype == jnp.int8
    assert plan.int8_scales[6].proj is not None
    assert plan.int8_scales[5].proj is None
    check_plan(plan)
    logits = jax.eval_shape(plan.jitted_forward(), jax.ShapeDtypeStruct(
        (2, 224, 224, 3), jnp.float32
    ))
    assert logits.shape == (2, 1000)


def test_resnet18_kernels_match_the_reference_at_224px(resnet18_plan):
    """The stem's kernel and the four residual kernels at ResNet-18's own
    shapes (224 px, whole-frame blocks, 64 to 512 channels, both kinds of
    join), run as the exact kernel program through the interpreter,
    against the hand-composed oracle: exact in 8 bits."""
    params = init_cnn(jax.random.PRNGKey(0), RESNET18)
    interp = compile_dhm(RESNET18, params, quant=INT8, backend="pallas_interpret")
    assert [g.layers for g in interp.fusion_groups] == [
        g.layers for g in resnet18_plan.fusion_groups
    ]
    x = _grid_frames((1, 224, 224, 3), seed=9)
    want = cnn_apply_reference(params, RESNET18, x, weight_bits=8, act_bits=8)
    np.testing.assert_array_equal(
        np.asarray(interp.features(x)),
        np.asarray(resnet18_plan.features(x)),
    )
    np.testing.assert_allclose(
        interp(x), want, rtol=0, atol=1e-6 * float(jnp.abs(want).max())
    )


@pytest.mark.parametrize("backend", ["pallas", "pallas_interpret"])
@pytest.mark.parametrize("quant", ["fp32", "fake8", "int8"])
def test_registry_verifies_residual_plans_clean(quant, backend):
    """Every plan, structure and resource invariant holds on the small
    residual net: one dot per conv and projection, one kernel per group,
    in-kernel stream quantization, integer dots, costed working sets
    (the stem's single-layer group is not re-costed as a pyramid)."""
    from repro.analysis.verify import verify_plan

    q = {"fp32": QuantSpec(), "fake8": QuantSpec(weight_bits=8, act_bits=8),
         "int8": INT8}[quant]
    plan = compile_dhm(TINY, _params(), quant=q, backend=backend)
    assert verify_plan(plan, scopes=("plan", "structure", "resource")) == []
