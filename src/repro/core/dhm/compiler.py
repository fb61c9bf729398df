"""Graph-driven DHM compiler: CNNTopology -> DPN -> stages -> execution plan.

This is the repo's rendering of HADDOC2's "network description in,
synthesizable actor graph out" pass as ONE lowering pipeline (the paper and
its companion report arXiv:1705.04543 frame direct hardware mapping as a
compiler problem). ``compile_dhm`` is the single entry point every consumer
routes through — ``cnn_apply``, the pipeline stage bodies, the examples and
the end-to-end benchmarks — so new topologies, backends or sharding
strategies plug in here instead of growing parallel hand-wired paths.

Lowering stages:

1. **Validate** the topology: ``act`` / ``pool`` / ``padding`` strings are
   checked against the fused-epilogue vocabulary at compile time, so a
   typo'd ``act="rleu"`` raises here with the valid options, not as an
   opaque KeyError deep inside a kernel trace.
2. **Cost** each conv layer as the paper-granularity dataflow process
   network would (``graph.conv_layer_flops``: the FLOPs of one conv
   engine per (map, channel), neuron sums, activation, pool and join
   actors, in closed form). The actor graph itself (``cnn_to_dpn``) is
   built only when a consumer reads ``CompiledDHM.graph``.
3. **Partition** the layers into ``n_stages`` contiguous stages with
   the exact min-max DP mapper, costed from those FLOPs — the
   critical-actor balancing the FPGA gets from its clock, solved here as a
   linear-partition problem. A residual net's basic block (``join``) is
   one unit: no stage boundary falls inside it.
4. **Fuse** each stage's layer run into maximal cross-layer fusion groups
   under a VMEM budget (``repro.core.dhm.fusion``): a group of consecutive
   conv layers is streamed through ONE fused pyramid kernel with all
   inter-layer feature slabs on-chip — the paper's no-external-memory
   dataflow property, recovered across layer boundaries. Groups that
   don't fit the budget fall back to single-layer kernel calls.
5. **Emit** per-stage fused-kernel closures (``stream_conv_pyramid`` /
   ``stream_conv_block`` actor chains) with the quantization *baked into
   the plan*: weights are
   fixed-point fake-quantized / pow2-projected once at compile time, and
   the feature-stream quantization runs inside the fused kernel epilogue
   (``act_bits``), never as a separate pass over HBM. The FC head lowers
   through the packed ``pow2_matmul`` kernel when ``quant.pow2_weights``
   (with straight-through gradients, so pow2 QAT still trains).

The resulting :class:`CompiledDHM` is a *plan*; execution lives in
``repro.core.dhm.engine``: single-device (sequential fused stages — the
default path under ``cnn_apply``), spatially on a mesh via
``pipeline_forward`` (``run_pipelined`` — heterogeneous stage shapes flow
through per-edge :class:`~repro.core.dhm.pipeline.StageIOSpec` geometry
emitted here), or behind the micro-batched serving ``Engine``. Each stage
owns a private device group exactly as each DHM actor owns private
silicon.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.dhm.fusion import (
    DEFAULT_VMEM_BUDGET,
    layer_units,
    plan_fusion_groups,
)
from repro.core.dhm.graph import DataflowGraph, cnn_to_dpn, conv_layer_flops
from repro.core.dhm.mapping import StageAssignment, partition_stages
from repro.core.dhm.pipeline import StageIOSpec
from repro.kernels.backends import DEFAULT_BACKEND, validate_backend
from repro.kernels.stream_conv.epilogue import ACTS, normalize_pool
from repro.kernels.stream_conv.halo import BLOCK_SPAN, JOINS

PADDINGS = ("SAME", "VALID")


class PlanCheckError(ValueError):
    """A compiled plan failed its self-check (non-finite baked parameters
    or inconsistent stage IO geometry) — the plan is not fit to serve.

    ``invariants`` names the registry IDs (``repro.analysis.invariants``)
    that failed, so demotion records and CI findings cite the same IDs.
    """

    def __init__(self, message: str, *, invariants=()):
        super().__init__(message)
        self.invariants = tuple(invariants)


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """The quantization contract baked into a compiled plan.

    ``weight_bits``: fixed-point fake-quant of all parameters (dynamic
    power-of-two scales, STE gradients — the paper's Q-format QAT).
    ``act_bits``: fixed-point width of the inter-actor feature stream,
    applied INSIDE the fused kernel epilogue (the paper quantizes the pixel
    flow, not just the parameters).
    ``pow2_weights``: project weights onto the {0, ±2^k} codebook; the FC
    head then lowers through the packed ``pow2_matmul`` kernel (when no
    additional ``weight_bits`` re-quantization is stacked on top).
    ``int8_compute``: execute the quantized plan in TRUE integer
    arithmetic: conv weights are baked to int8 codes + a static pow2
    scale, the feature stream enters each kernel as int8 codes, and the
    conv matmuls contract integers into int32 accumulators
    (``preferred_element_type``) with the requantization to the stream's
    ``act_bits`` grid fused into the existing epilogue. Requires a weight
    AND act width (<= 8) for every conv layer. Int8 plans are
    forward-only (serving), not QAT paths.
    ``per_layer_bits``: per-conv-layer bit widths (a tuple, one entry per
    conv layer) overriding BOTH ``weight_bits`` and ``act_bits`` for that
    layer — the paper's Fig. 3 bitwidth sweep as a compile-time plan
    attribute (see ``repro.core.quant.bitwidth_search``).
    """

    weight_bits: Optional[int] = None
    act_bits: Optional[int] = None
    pow2_weights: bool = False
    int8_compute: bool = False
    per_layer_bits: Optional[tuple] = None

    def __post_init__(self):
        for name in ("weight_bits", "act_bits"):
            v = getattr(self, name)
            if v is not None and v < 2:
                raise ValueError(f"{name} must be >= 2 (or None), got {v}")
        if self.per_layer_bits is not None:
            object.__setattr__(
                self, "per_layer_bits", tuple(self.per_layer_bits)
            )
            for b in self.per_layer_bits:
                if not isinstance(b, int) or isinstance(b, bool) or b < 2:
                    raise ValueError(
                        f"per_layer_bits entries must be ints >= 2, got "
                        f"{self.per_layer_bits}"
                    )
        if self.int8_compute:
            n = (
                len(self.per_layer_bits)
                if self.per_layer_bits is not None
                else 1
            )
            for i in range(n):
                wb, ab = self.conv_weight_bits(i), self.conv_act_bits(i)
                if wb is None or ab is None:
                    raise ValueError(
                        "int8_compute requires a weight AND act bit width "
                        "for every conv layer (weight_bits/act_bits or "
                        "per_layer_bits)"
                    )
                if wb > 8 or ab > 8:
                    raise ValueError(
                        f"int8_compute requires all conv bit widths <= 8, "
                        f"got weight={wb} act={ab} for layer {i}"
                    )

    def conv_weight_bits(self, i: int) -> Optional[int]:
        """Weight bit width of conv layer ``i`` (per-layer override wins)."""
        if self.per_layer_bits is not None:
            return self.per_layer_bits[i]
        return self.weight_bits

    def conv_act_bits(self, i: int) -> Optional[int]:
        """Feature-stream bit width AFTER conv layer ``i`` (per-layer
        override wins)."""
        if self.per_layer_bits is not None:
            return self.per_layer_bits[i]
        return self.act_bits

    @property
    def mixed_bitwidth(self) -> bool:
        """Whether the plan carries per-layer bit choices."""
        return self.per_layer_bits is not None

    @property
    def stream_bits(self) -> int:
        """Fixed-point width used to size DPN line buffers and streams."""
        if self.per_layer_bits is not None:
            return max(self.per_layer_bits)
        return self.act_bits or self.weight_bits or 32

    @property
    def packed_fc_head(self) -> bool:
        """Whether the FC head lowers through the packed pow2 kernel.

        With ``weight_bits`` stacked on top of the pow2 projection the
        weights leave the pure codebook, so the head falls back to the
        dense (projected + fake-quantized) matmul. ``per_layer_bits``
        only governs conv layers, so it does not demote the head.
        """
        return self.pow2_weights and self.weight_bits is None


def _spec_fields(spec) -> dict:
    """The layer vocabulary of a (duck-typed) conv-layer spec, with the
    generalized fields defaulted for specs that predate them."""
    return dict(
        padding=spec.padding,
        act=spec.act,
        pool=spec.pool,
        pool_stride=getattr(spec, "pool_stride", None),
        stride=getattr(spec, "stride", 1),
        pool_pad=getattr(spec, "pool_pad", 0),
    )


def _validate_layer(
    where: str, *, padding: str, act: str, pool: int,
    pool_stride: int | None = None, stride: int = 1, pool_pad: int = 0,
) -> None:
    """Compile-time validation of the layer vocabulary — a typo raises
    here with the options listed, not as a trace-time KeyError."""
    if act not in ACTS:
        raise ValueError(f"{where}: unknown act {act!r}; expected one of {ACTS}")
    try:
        normalize_pool(pool, pool_stride)
    except ValueError as e:
        raise ValueError(f"{where}: {e}") from None
    if not isinstance(stride, int) or isinstance(stride, bool) or stride < 1:
        raise ValueError(
            f"{where}: conv stride must be a positive int, got {stride!r}"
        )
    if padding not in PADDINGS:
        raise ValueError(
            f"{where}: unknown padding {padding!r}; expected one of {PADDINGS}"
        )
    pw, _ = normalize_pool(pool, pool_stride)
    if not isinstance(pool_pad, int) or pool_pad < 0 or 2 * pool_pad > pw:
        raise ValueError(
            f"{where}: pool_pad must be an int in [0, window // 2] "
            f"(-inf padding of a {pw}x{pw} pool), got {pool_pad!r}"
        )


def _validate_joins(topo) -> None:
    """Residual joins close basic blocks of ``BLOCK_SPAN`` layers that do
    not overlap or pool; an identity join needs the block's input shape."""
    layers = topo.conv_layers
    for j, spec in enumerate(layers):
        join = getattr(spec, "join", "none")
        if join not in JOINS:
            raise ValueError(
                f"{topo.name} conv layer {j}: unknown join {join!r}; "
                f"expected one of {JOINS}"
            )
        if join == "none":
            continue
        where = f"{topo.name} conv layer {j} ({join} join)"
        first = j - BLOCK_SPAN + 1
        if first < 0:
            raise ValueError(f"{where}: its block starts before layer 0")
        for i in range(first, j + 1):
            if i < j and getattr(layers[i], "join", "none") != "none":
                raise ValueError(f"{where}: overlaps the block closed at {i}")
            if layers[i].pool:
                raise ValueError(f"{where}: layer {i} of the block pools")
        stride = 1
        for i in range(first, j + 1):
            stride *= getattr(layers[i], "stride", 1)
        c_in = topo.input_channels if first == 0 else layers[first - 1].n_out
        if join == "identity" and (stride != 1 or c_in != spec.n_out):
            raise ValueError(
                f"{where}: the block changes the shape (stride {stride}, "
                f"{c_in} -> {spec.n_out} channels); use a project join"
            )


def validate_topology(topo) -> None:
    """Validate every conv layer of a CNNTopology at compile time: the
    layer vocabulary, the residual blocks, and (when the topology exposes
    shape methods) that every layer keeps positive spatial dims — a pool
    window larger than its conv output raises here, instead of silently
    emitting a zero-sized frame."""
    for li, spec in enumerate(topo.conv_layers):
        _validate_layer(f"{topo.name} conv layer {li}", **_spec_fields(spec))
    _validate_joins(topo)
    if not hasattr(topo, "input_shape"):
        return
    h, w = topo.input_shape
    for li, spec in enumerate(topo.conv_layers):
        where = f"{topo.name} conv layer {li}"
        h_c, w_c = spec.conv_hw(h, w)
        if h_c < 1 or w_c < 1:
            raise ValueError(
                f"{where}: conv output {h_c}x{w_c} is empty for a {h}x{w} "
                f"input (kernel={spec.kernel}, stride={spec.stride}, "
                f"padding={spec.padding})"
            )
        pw, _ = spec.pool_cfg
        if pw and (h_c < pw or w_c < pw):
            raise ValueError(
                f"{where}: conv output {h_c}x{w_c} too small for a "
                f"{pw}x{pw} pool window"
            )
        h, w = spec.out_hw(h, w)


@functools.lru_cache(maxsize=64)
def _cached_dpn(topo, bits: int) -> DataflowGraph:
    """CNNTopology is a frozen (hashable) dataclass, so the actor-graph
    expansion — thousands of actors for CIFAR-sized nets, over a million
    for ResNet-18 — is built once per (topology, bit-width), and only for
    a consumer that reads ``CompiledDHM.graph``."""
    return cnn_to_dpn(topo, bits=bits)


@functools.lru_cache(maxsize=256)
def _cached_layout(topo, n_stages: int) -> StageAssignment:
    """The DP partition of the layers into ``n_stages``, costed by
    ``graph.conv_layer_flops`` (the actor graph's per-layer FLOPs in
    closed form, so no graph is built), by units: a residual block's
    layers stay in one stage, so a skip edge never crosses a stage
    boundary and the plan stays a chain of stages. Memoized per
    (topology, n_stages)."""
    costs = conv_layer_flops(topo)
    units = layer_units(topo.conv_layers, range(len(costs)))
    by_unit = partition_stages(
        [sum(costs[li] for li in u) for u in units], n_stages
    )
    bounds = tuple(
        units[b][0] if b < len(units) else len(costs)
        for b in by_unit.boundaries
    )
    return StageAssignment(
        n_layers=len(costs), boundaries=bounds,
        stage_costs=by_unit.stage_costs,
    )


def emit_conv_stage(
    specs: Sequence,
    *,
    backend: Optional[str] = None,
    act_bits=None,  # int | None | per-layer tuple
    int8_scales: Optional[Sequence] = None,  # per-layer Int8Scales | None
    block_r: int = 8,
    block_w: int = 0,
    block_c: int = 0,
    block_n: int = 0,
    groups: Optional[Sequence] = None,
    first_layer: int = 0,
) -> Callable:
    """Emit one pipeline-stage body: a chain of fused conv actor chains.

    ``specs`` is a sequence of conv-layer specs (anything with ``padding``,
    ``act``, ``pool`` attributes — e.g. ``ConvLayerSpec``; the generalized
    ``stride``/``pool_stride`` fields default to 1/window when absent).
    ``groups`` partitions the stage's layers into fusion groups — a
    sequence of ``(local_layer_indices, block_rows)`` pairs covering the
    stage contiguously. A multi-layer group lowers through ONE
    ``stream_conv_pyramid`` call (inter-layer slabs VMEM-resident);
    singleton groups lower through today's single-layer
    ``stream_conv_block`` (with its channel/width blocking knobs).
    ``first_layer`` is the plan's index of the stage's first layer: a
    group that carries residual joins runs a kernel named after the plan
    layers it computes (``stream_conv_residual_pallas_l<first>_<last>``).
    ``groups=None`` means the minimal groups — every layer alone but a
    residual net's basic blocks, one group each (a join needs its block's
    input in the same kernel).

    ``act_bits`` may be a single width for the whole stage or a per-layer
    tuple (mixed-bitwidth plans); ``int8_scales`` (one
    ``epilogue.Int8Scales`` per stage layer) switches the kernels to the
    true-integer rendering — int8 weight codes are then expected in
    ``params``.

    The returned ``stage_fn(params, x)`` runs conv -> bias -> act (-> pool
    -> stream quant) per layer. ``params`` is a list with one
    ``{"w": (K, K, C, N), "b": (N,)}`` dict per layer (a bare dict is
    accepted for single-layer stages).
    """
    from repro.kernels.stream_conv import stream_conv_block, stream_conv_pyramid

    specs = tuple(specs)
    if not specs:
        raise ValueError("a conv stage needs at least one layer spec")
    bits = (
        tuple(act_bits)
        if isinstance(act_bits, (tuple, list))
        else (act_bits,) * len(specs)
    )
    if len(bits) != len(specs):
        raise ValueError(
            f"act_bits tuple has {len(bits)} entries for a "
            f"{len(specs)}-layer stage"
        )
    scales = None if int8_scales is None else tuple(int8_scales)
    if scales is not None and len(scales) != len(specs):
        raise ValueError(
            f"int8_scales has {len(scales)} entries for a "
            f"{len(specs)}-layer stage"
        )
    layer_kw = []
    for li, spec in enumerate(specs):
        fields = _spec_fields(spec)
        _validate_layer(f"stage layer {li}", **fields)
        layer_kw.append(fields)
    resolved = validate_backend(
        DEFAULT_BACKEND if backend is None else backend
    )
    if groups is None:
        group_plan = tuple(
            (u, 0) for u in layer_units(specs, range(len(specs)))
        )
    else:
        group_plan = tuple((tuple(g), int(br)) for g, br in groups)
        covered = [li for g, _ in group_plan for li in g]
        if covered != list(range(len(specs))):
            raise ValueError(
                f"fusion groups {group_plan} do not cover stage layers "
                f"0..{len(specs) - 1} contiguously"
            )

    def stage_fn(params, x):
        layer_params = [params] if isinstance(params, dict) else list(params)
        if len(layer_params) != len(specs):
            raise ValueError(
                f"stage has {len(specs)} layers but got "
                f"{len(layer_params)} param dicts"
            )
        for g, block_rows in group_plan:
            if len(g) == 1:
                p = layer_params[g[0]]
                x = stream_conv_block(
                    x,
                    p["w"],
                    p["b"],
                    act_bits=bits[g[0]],
                    int8_scales=None if scales is None else scales[g[0]],
                    backend=resolved,
                    block_r=block_r,
                    block_w=block_w,
                    block_c=block_c,
                    block_n=block_n,
                    **layer_kw[g[0]],
                )
            else:
                x = stream_conv_pyramid(
                    x,
                    [layer_params[li]["w"] for li in g],
                    [layer_params[li]["b"] for li in g],
                    projections=[
                        (layer_params[li]["proj"]["w"],
                         layer_params[li]["proj"]["b"])
                        if "proj" in layer_params[li] else None
                        for li in g
                    ],
                    layers=[specs[li] for li in g],
                    act_bits=tuple(bits[li] for li in g),
                    int8_scales=(
                        None
                        if scales is None
                        else tuple(scales[li] for li in g)
                    ),
                    block_rows=block_rows,
                    backend=resolved,
                    first_layer=first_layer + g[0],
                )
        return x

    return stage_fn


# ---------------------------------------------------------------------------
# Quantization baking


def _bake_conv_params(conv_params, quant: QuantSpec):
    """Fold each conv's eval-mode batch norm into its kernel and bias
    (``models.cnn.fold_bn``; a projection shortcut's too), then mirror the
    fake-quant reference composition order: pow2 projection (STE) first,
    then fixed-point fake-quant of every tensor — so the folded kernel is
    what gets quantized, as deployments do.

    Returns ``(baked_params, w_scales)``. Under ``quant.int8_compute`` the
    weights bake to int8 CODES on the same dynamic pow2 grid
    ``fake_quant_dynamic`` would use (``codes * scale ==
    fake_quant_dynamic(w, bits)`` exactly), and ``w_scales`` carries, per
    layer, the static pow2 scale the kernels fold into their int32
    dequantization — a ``(conv, projection)`` pair for a layer with a
    projection shortcut; otherwise ``w_scales`` is None.
    """
    from repro.core.quant.fixed_point import (
        dynamic_spec,
        fake_quant_dynamic,
        quantize_fixed,
    )
    from repro.core.quant.pow2 import project_pow2_ste
    from repro.models.cnn import fold_bn

    def bake(w, b, wb):
        if quant.pow2_weights:
            w = project_pow2_ste(w)
        if quant.int8_compute:
            wspec = dynamic_spec(w, wb)
            return (
                quantize_fixed(w, wspec).astype(jnp.int8),
                fake_quant_dynamic(b, wb),
                float(wspec.scale),
            )
        if wb is not None:
            w = fake_quant_dynamic(w, wb)
            b = fake_quant_dynamic(b, wb)
        return w, b, None

    out, w_scales = [], []
    for i, p in enumerate(conv_params):
        p = fold_bn(p)
        wb = quant.conv_weight_bits(i)
        w, b, scale = bake(p["w"], p["b"], wb)
        baked = {"w": w, "b": b}
        if "proj" in p:
            pw_, pb, p_scale = bake(p["proj"]["w"], p["proj"]["b"], wb)
            baked["proj"] = {"w": pw_, "b": pb}
            scale = (scale, p_scale)
        out.append(baked)
        w_scales.append(scale)
    return tuple(out), (tuple(w_scales) if quant.int8_compute else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pow2_linear_ste(x, w, backend, x_spec=None):
    """Forward through the packed pow2 kernel (x @ decode(pack(w)));
    backward straight-through, as if the layer were ``x @ project_pow2(w)``
    — so pow2 QAT keeps training while serving-path lowering is exercised
    in the forward pass. A static ``x_spec`` (the activation's fixed-point
    grid) forwards through the true-integer shift-add rendering where the
    backend supports it (see ``pow2_matmul``)."""
    from repro.kernels.pow2_matmul import pow2_matmul, quantize_weights

    packed, scale = quantize_weights(w)
    return pow2_matmul(x, packed, scale, backend=backend, x_spec=x_spec)


def _pow2_linear_ste_fwd(x, w, backend, x_spec=None):
    from repro.core.quant.pow2 import project_pow2

    return (
        _pow2_linear_ste(x, w, backend, x_spec),
        (x, project_pow2(w, channel_axis=1)),
    )


def _pow2_linear_ste_bwd(backend, x_spec, res, g):
    x, w_proj = res
    return (
        jnp.dot(g, w_proj.T.astype(g.dtype)),
        jnp.dot(x.T.astype(g.dtype), g),  # STE: identity through the projection
    )


_pow2_linear_ste.defvjp(_pow2_linear_ste_fwd, _pow2_linear_ste_bwd)


def _emit_head(
    fc_params, quant: QuantSpec, backend: str, head_in_bits=None,
    global_pool: bool = False,
) -> Callable:
    """Emit the classifier head: flatten (or, with ``global_pool``,
    average the last feature map over H and W) -> FC stack, with the same
    quantization contract as the conv stages (tanh + feature-stream quant
    between hidden layers; logits unquantized, as in the reference; the
    pooled average is not requantized).

    Under ``int8_compute`` with a packed pow2 head, each FC forwards
    through the integer shift-add rendering: the first FC's input grid is
    the LAST conv layer's stream spec (``head_in_bits``), later FCs see
    the head's own ``act_bits`` stream quant.
    """
    from repro.core.quant.fixed_point import fake_quant_dynamic, fake_quant_ste
    from repro.core.quant.pow2 import project_pow2_ste
    from repro.kernels.stream_conv.epilogue import stream_quant_spec

    baked = []
    for p in fc_params:
        w, b = p["w"], p["b"]
        if quant.pow2_weights and not quant.packed_fc_head:
            w = project_pow2_ste(w)
        if quant.weight_bits is not None:
            w = fake_quant_dynamic(w, quant.weight_bits)
            b = fake_quant_dynamic(b, quant.weight_bits)
        baked.append({"w": w, "b": b})

    # Same Q-format as the in-kernel stream quantization of the conv stages.
    qact_spec = (
        stream_quant_spec(quant.act_bits) if quant.act_bits is not None else None
    )
    int_head = (
        quant.int8_compute
        and quant.packed_fc_head
        and quant.act_bits is not None
    )
    # The activation grid each FC's input lives on: the conv stream for the
    # first FC, the head's own stream quant after that.
    first_spec = (
        stream_quant_spec(
            head_in_bits if head_in_bits is not None else quant.act_bits
        )
        if int_head
        else None
    )

    def head_fn(h):
        if global_pool:
            h = jnp.mean(h, axis=(1, 2))
        h = h.reshape(h.shape[0], -1)
        for i, p in enumerate(baked):
            if quant.packed_fc_head:
                x_spec = (
                    (first_spec if i == 0 else qact_spec) if int_head else None
                )
                h = _pow2_linear_ste(h, p["w"], backend, x_spec) + p["b"]
            elif global_pool:
                # The pooled average is off every grid, so the MXU's
                # default one-pass bf16 would round it: full float32.
                h = jnp.dot(h, p["w"], precision=jax.lax.Precision.HIGHEST) + p["b"]
            else:
                h = h @ p["w"] + p["b"]
            if i < len(baked) - 1:
                h = jnp.tanh(h)
                if qact_spec is not None:
                    h = fake_quant_ste(h, qact_spec)
        return h

    return head_fn


# ---------------------------------------------------------------------------
# The compiled plan


@dataclasses.dataclass(frozen=True)
class CompiledStage:
    """One pipeline stage: a contiguous run of conv layers lowered as a
    chain of fusion groups (each group one fused kernel invocation)."""

    index: int
    conv_layers: tuple  # conv-layer indices owned by this stage
    specs: tuple  # the ConvLayerSpec per owned layer
    fn: Callable  # (params_list, x) -> y
    cost_flops: float  # summed actor payloads (the mapper's stage cost)
    groups: tuple = ()  # FusionGroup per kernel invocation in this stage
    io: Optional[StageIOSpec] = None  # (H, W, C) activation edge geometry


@dataclasses.dataclass(frozen=True)
class CompiledDHM:
    """Executable lowering of a CNN topology: quantized parameters +
    per-stage fused-kernel closures + the FC head, plus the IR artifacts
    (stage assignment; the DPN graph on demand) the lowering went
    through."""

    topo: object
    quant: QuantSpec
    backend: str
    assignment: StageAssignment
    stages: tuple
    conv_params: tuple  # per conv layer {"w", "b"}, quantization baked
    head_fn: Callable
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    int8_scales: tuple = ()  # per conv layer Int8Scales when int8_compute

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @functools.cached_property
    def graph(self) -> DataflowGraph:
        """The paper-granularity actor graph of the topology (built on
        first read; compiling the plan does not need it)."""
        return _cached_dpn(self.topo, self.quant.stream_bits)

    def stage_quant_kwargs(self, stage: int) -> dict:
        """The quantization kwargs ``emit_conv_stage`` needs to re-emit
        stage ``stage``'s body (degradation-ladder rebuilds must inherit
        the plan's int8/mixed-bitwidth contract, not just ``act_bits``)."""
        st = self.stages[stage]
        if not self.int8_scales and not self.quant.mixed_bitwidth:
            return {"act_bits": self.quant.act_bits}
        kw = {
            "act_bits": tuple(
                self.quant.conv_act_bits(i) for i in st.conv_layers
            )
        }
        if self.int8_scales:
            kw["int8_scales"] = tuple(
                self.int8_scales[i] for i in st.conv_layers
            )
        return kw

    @property
    def fusion_groups(self) -> tuple:
        """Every FusionGroup of the plan, in execution order."""
        return tuple(g for st in self.stages for g in st.groups)

    def stage_params(self, stage: int) -> list:
        return [self.conv_params[i] for i in self.stages[stage].conv_layers]

    def self_check(self) -> None:
        """Health-probe the plan (see :func:`check_plan`); raises
        :class:`PlanCheckError` when the plan is not fit to serve."""
        check_plan(self)

    def features(self, x: jax.Array) -> jax.Array:
        """Run the conv stages sequentially (single-device execution)."""
        for st in self.stages:
            x = st.fn(self.stage_params(st.index), x)
        return x

    def jitted_forward(self, *, donate: bool = False) -> Callable:
        """The plan's cached end-to-end jitted closure (see
        ``repro.core.dhm.engine.plan_jitted_forward``, where execution
        lives). ``donate=True`` donates the input buffer — the serving
        ``Engine``'s double-buffered path."""
        from repro.core.dhm.engine import plan_jitted_forward

        return plan_jitted_forward(self, donate=donate)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (B, H, W, C) NHWC -> logits (B, n_classes). Runs the cached
        end-to-end jitted closure (``jitted_forward``)."""
        return self.jitted_forward()(x)

    # -- spatial (mesh) execution ------------------------------------------

    def pipeline_spec(self):
        """Per-stage closures + params + per-edge activation geometry
        (:class:`StageIOSpec`) for the heterogeneous streaming executor.
        Stages may freely pool/stride down and grow channels between
        boundaries — the executor groups the interior edges into
        shape classes (see :meth:`edge_plan`) and each stage computes on
        its exact geometry."""
        from repro.core.dhm.engine import pipeline_spec

        return pipeline_spec(self)

    def edge_plan(self, *, mode: str = "auto", max_classes: int = 4):
        """How this plan's interior stage-boundary activations would
        travel over ICI: the :class:`~repro.core.dhm.pipeline.EdgePlan`
        (shape classes, per-class partial-permutation pairs, padding
        fraction) the executor builds from the :class:`StageIOSpec`
        chain. Inspect ``.mode`` to see whether the plan streams
        exact-shape edges or falls back to the boxed max-shape buffer."""
        from repro.core.dhm.pipeline import plan_edges

        return plan_edges(
            [st.io for st in self.stages], mode=mode, max_classes=max_classes
        )

    def edge_shapes(self) -> tuple:
        """The exact per-interior-edge activation element shapes (stage
        s -> s+1), straight off the :class:`StageIOSpec` chain."""
        return tuple(
            tuple(self.stages[s].io.out_shape)
            for s in range(self.n_stages - 1)
        )

    def run_pipelined(
        self, microbatches, *, mesh, cfg=None, data_axis=None,
        overlap=False, edge_mode="auto",
    ):
        """Stream (M, mb, H, W, C) µbatches through the conv stages on a
        mesh (one device group per stage; with ``data_axis`` the µbatch
        dim is additionally batch-sharded on a 2D ``(stage, data)`` mesh;
        ``overlap``/``edge_mode`` select the double-buffered schedule and
        the ICI edge path). Returns the feature stream; apply ``head_fn``
        after re-flattening for logits."""
        from repro.core.dhm.engine import run_pipelined

        return run_pipelined(
            self, microbatches, mesh=mesh, cfg=cfg, data_axis=data_axis,
            overlap=overlap, edge_mode=edge_mode,
        )


def check_plan(plan: CompiledDHM) -> None:
    """Self-check a compiled plan: the ``plan``-scope invariants of the
    ``repro.analysis`` registry — every baked parameter finite (V301),
    the per-stage IO geometry chains (V302), every emitted stage body and
    the head produce the shapes their :class:`StageIOSpec` promises via
    ``jax.eval_shape`` (V303/V304) — no FLOPs spent.

    Raises :class:`PlanCheckError` carrying the failed invariant IDs.
    This doubles as the serving engine's health probe: a rung of the
    degradation ladder is only promoted into service after the plan it
    runs passes this check, so serving and CI enforce the SAME registry.
    """
    from repro.analysis.verify import check_plan as _registry_check

    _registry_check(plan)


def compile_dhm(
    topo,
    params: dict,
    *,
    quant: QuantSpec = QuantSpec(),
    n_stages: int = 1,
    backend: Optional[str] = None,
    block_r: int = 8,
    block_w: int = 0,
    block_c: int = 0,
    block_n: int = 0,
    vmem_budget: Optional[int] = None,
) -> CompiledDHM:
    """Lower a CNNTopology + params to an executable DHM plan.

    Args:
      topo: a ``repro.models.cnn.CNNTopology`` (or any object with the same
        ``conv_layers`` / ``conv_shapes()`` duck type).
      params: ``{"conv": [{"w", "b"}...], "fc": [{"w", "b"}...]}`` as built
        by ``init_cnn``. Quantization per ``quant`` is baked into the plan
        here, once.
      quant: the :class:`QuantSpec` contract.
      n_stages: contiguous pipeline stages to partition the conv stack into
        (1 = the whole feature extractor as one sequential plan).
      backend: kernel backend enum (``repro.kernels.backends``); None means
        the compiled default.
      vmem_budget: per-block VMEM byte budget of the cross-layer fusion
        planner (``repro.core.dhm.fusion``). Within each stage the planner
        walks the DPN's conv layers and emits maximal contiguous fusion
        groups whose costed working set (weights + composed-halo feature
        slabs + tap operands) fits the budget; each multi-layer group runs
        as ONE fused pyramid kernel with inter-layer slabs VMEM-resident —
        the paper's no-external-memory dataflow across layer boundaries.
        ``None`` means :data:`~repro.core.dhm.fusion.DEFAULT_VMEM_BUDGET`
        (~one TPU core's VMEM; under it every paper topology's feature
        extractor fuses into a single group); ``0`` disables fusion, which
        reproduces the per-layer-stage plan exactly (each layer one
        ``stream_conv_block`` call with the ``block_*`` knobs).
    """
    validate_topology(topo)
    resolved = validate_backend(DEFAULT_BACKEND if backend is None else backend)
    n_conv = len(topo.conv_layers)
    n_units = len(layer_units(topo.conv_layers, range(n_conv)))
    if not 1 <= n_stages <= n_units:
        raise ValueError(
            f"n_stages must be in [1, {n_units}] for {topo.name}, got {n_stages}"
        )
    if quant.per_layer_bits is not None and len(quant.per_layer_bits) != n_conv:
        raise ValueError(
            f"per_layer_bits has {len(quant.per_layer_bits)} entries but "
            f"{topo.name} has {n_conv} conv layers"
        )
    if quant.int8_compute:
        for i in range(n_conv):
            wb, ab = quant.conv_weight_bits(i), quant.conv_act_bits(i)
            if wb is None or ab is None or wb > 8 or ab > 8:
                raise ValueError(
                    f"int8_compute needs weight/act bits <= 8 for every "
                    f"conv layer; layer {i} has weight={wb} act={ab}"
                )
    resolved_budget = (
        DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    )
    if resolved_budget < 0:
        raise ValueError(
            f"vmem_budget must be >= 0 (0 disables fusion), got {vmem_budget}"
        )

    assignment = _cached_layout(topo, n_stages)

    conv_params, w_scales = _bake_conv_params(params["conv"], quant)
    if quant.int8_compute:
        from repro.kernels.stream_conv.epilogue import Int8Scales

        # Layer i's input stream is layer i-1's quantized output; layer 0
        # quantizes the frame onto its own stream grid (the plan contract
        # for int8 input ingestion). A projection reads its block's input
        # stream, the input of layer i - BLOCK_SPAN + 1.
        def in_bits(i):
            return quant.conv_act_bits(max(i - 1, 0))

        def scales(i):
            ws = w_scales[i]
            if not isinstance(ws, tuple):
                return Int8Scales(in_bits=in_bits(i), w_scale=ws)
            proj = Int8Scales(
                in_bits=in_bits(i - BLOCK_SPAN + 1), w_scale=ws[1]
            )
            return Int8Scales(in_bits=in_bits(i), w_scale=ws[0], proj=proj)

        int8_scales = tuple(scales(i) for i in range(n_conv))
    else:
        int8_scales = ()
    elem_bytes = 1 if quant.int8_compute else 4
    per_layer_act = tuple(quant.conv_act_bits(i) for i in range(n_conv))
    varies = quant.mixed_bitwidth or quant.int8_compute

    stages = []
    h, w = topo.input_shape
    c = topo.input_channels
    for s in range(n_stages):
        idxs = tuple(assignment.layers_of_stage(s))
        specs = tuple(topo.conv_layers[i] for i in idxs)
        in_shape = (h, w, c)
        for spec in specs:
            h, w = spec.out_hw(h, w)
            c = spec.n_out
        io = StageIOSpec(in_shape=in_shape, out_shape=(h, w, c))
        groups = plan_fusion_groups(
            topo, idxs, vmem_budget=resolved_budget, elem_bytes=elem_bytes
        )
        local_groups = tuple(
            (tuple(li - idxs[0] for li in g.layers), g.block_rows)
            for g in groups
        )
        stages.append(
            CompiledStage(
                index=s,
                conv_layers=idxs,
                specs=specs,
                fn=emit_conv_stage(
                    specs,
                    backend=resolved,
                    act_bits=(
                        tuple(per_layer_act[i] for i in idxs)
                        if varies
                        else quant.act_bits
                    ),
                    int8_scales=(
                        tuple(int8_scales[i] for i in idxs)
                        if quant.int8_compute
                        else None
                    ),
                    block_r=block_r,
                    block_w=block_w,
                    block_c=block_c,
                    block_n=block_n,
                    groups=local_groups,
                    first_layer=idxs[0],
                ),
                cost_flops=assignment.stage_costs[s],
                groups=groups,
                io=io,
            )
        )

    head_fn = _emit_head(
        params["fc"], quant, resolved, head_in_bits=per_layer_act[-1],
        global_pool=getattr(topo, "global_pool", False),
    )
    return CompiledDHM(
        topo=topo,
        quant=quant,
        backend=resolved,
        assignment=assignment,
        stages=tuple(stages),
        conv_params=conv_params,
        head_fn=head_fn,
        vmem_budget=resolved_budget,
        int8_scales=int8_scales,
    )
