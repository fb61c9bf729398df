"""The named invariant registry the plan verifier enforces.

Every invariant is a pure function ``(ProbeContext) -> list[Finding]``
registered under a stable ID. IDs are grouped by scope:

- ``plan``  (V3xx): serving-fitness checks — finite baked params and
  consistent stage IO geometry via ``jax.eval_shape``. This scope IS the
  ``check_plan`` rung-probe: the serving engine and CI enforce the same
  registry.
- ``structure`` (V0xx): jaxpr-structure proofs — one ``dot_general`` per
  conv layer, one ``pallas_call`` per fusion group, no conv primitive,
  no dtype drift, no host transfers, donation actually declared,
  in-kernel stream quantization.
- ``resource`` (V2xx): working-set fit — planner budget respected, cost
  model self-consistent, and the *traced* aval footprint bounded by the
  recorded working set.
- ``pipeline`` (V1xx): the traced ``run_pipelined`` closure contains
  exactly the ``EdgePlan``'s collectives — per-class exact-shape
  ppermutes covering the S-1 interior edges; boxed fallback is flagged
  with its padding fraction.

Invariants self-gate: one that does not apply to the probed artifact
(e.g. a pallas-body check against a ``ref``-backend plan) returns no
findings. Add a new invariant with the :func:`invariant` decorator —
it is picked up by the CLI, ``check_plan``, and the tests without
further wiring.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

from repro.analysis.jaxpr_utils import (
    aval_bytes,
    count_primitive,
    count_primitive_in_pallas,
    find_primitive,
    float_avals,
    iter_eqns,
)

SCOPES = ("plan", "structure", "resource", "pipeline")

# Backends whose lowering goes through the pallas stream-conv kernels
# (the structural one-dot-per-layer contract); "ref" lowers through lax
# reference ops and is exempt from kernel-structure invariants.
_PALLAS_BACKENDS = ("pallas", "pallas_interpret")

# On CPU the default "pallas" backend falls back to XLA, so pallas_call
# only appears in traces under the interpret backend — body-structure
# invariants run against the interpret probe plan the CLI compiles.
_INTERPRET_BACKEND = "pallas_interpret"

# Primitives that would smuggle a host round-trip into the serving hot
# path (V005).
_HOST_TRANSFER_PRIMS = frozenset(
    {
        "device_put", "pure_callback", "io_callback", "debug_callback",
        "callback", "infeed", "outfeed",
    }
)


@dataclasses.dataclass(frozen=True)
class Invariant:
    id: str
    name: str
    scope: str
    doc: str
    fn: Callable


REGISTRY: Dict[str, Invariant] = {}


def invariant(id: str, *, name: str, scope: str):
    """Register an invariant check under a stable ID."""
    if scope not in SCOPES:
        raise ValueError(f"scope must be one of {SCOPES}, got {scope!r}")

    def deco(fn):
        if id in REGISTRY:
            raise ValueError(f"duplicate invariant id {id!r}")
        REGISTRY[id] = Invariant(
            id=id, name=name, scope=scope, doc=fn.__doc__ or "", fn=fn
        )
        return fn

    return deco


def by_scope(*scopes: str):
    return [inv for inv in REGISTRY.values() if inv.scope in scopes]


# ---------------------------------------------------------------------------
# plan scope (V3xx) — the check_plan rung-probe set


@invariant("V301", name="finite-params", scope="plan")
def _finite_params(ctx):
    """Every baked conv parameter is finite — NaN/Inf weights must never
    reach a serving rung."""
    import jax.numpy as jnp

    import jax

    out = []
    for li, p in enumerate(ctx.plan.conv_params):
        for path, v in jax.tree_util.tree_leaves_with_path(p):
            if not bool(jnp.isfinite(v).all()):
                k = "/".join(str(getattr(e, "key", e)) for e in path)
                out.append(ctx.error(
                    "V301",
                    f"conv layer {li} parameter {k!r} contains non-finite "
                    "values — the plan cannot serve",
                ))
    return out


@invariant("V302", name="io-chain", scope="plan")
def _io_chain(ctx):
    """StageIOSpec geometry is present, starts at the topology input, and
    chains stage-to-stage."""
    plan = ctx.plan
    ios = [st.io for st in plan.stages]
    if any(io is None for io in ios):
        return [ctx.error("V302", "plan stages miss StageIOSpec geometry")]
    out = []
    h, w = plan.topo.input_shape
    if tuple(ios[0].in_shape) != (h, w, plan.topo.input_channels):
        out.append(ctx.error(
            "V302",
            f"stage 0 input {ios[0].in_shape} does not match the topology "
            f"input {(h, w, plan.topo.input_channels)}",
        ))
    for s in range(len(ios) - 1):
        if tuple(ios[s].out_shape) != tuple(ios[s + 1].in_shape):
            out.append(ctx.error(
                "V302",
                f"stage {s} output {ios[s].out_shape} does not chain into "
                f"stage {s + 1} input {ios[s + 1].in_shape}",
            ))
    return out


@invariant("V303", name="stage-io-shape", scope="plan")
def _stage_io_shape(ctx):
    """Each emitted stage body, abstractly interpreted on its declared
    input (``jax.eval_shape`` — no FLOPs), produces exactly the shape its
    StageIOSpec promises."""
    import jax
    import jax.numpy as jnp

    plan = ctx.plan
    out = []
    for st in plan.stages:
        if st.io is None:
            continue  # V302 reports the missing geometry
        try:
            got = jax.eval_shape(
                st.fn,
                plan.stage_params(st.index),
                jax.ShapeDtypeStruct(
                    (1,) + tuple(st.io.in_shape), jnp.float32
                ),
            )
        except Exception as e:  # noqa: BLE001 — surfaced as a finding
            out.append(ctx.error(
                "V303",
                f"stage {st.index} body fails to trace on its declared "
                f"input {st.io.in_shape}: {e}",
            ))
            continue
        if tuple(got.shape[1:]) != tuple(st.io.out_shape):
            out.append(ctx.error(
                "V303",
                f"stage {st.index} body produces {tuple(got.shape[1:])}, "
                f"but its StageIOSpec promises {tuple(st.io.out_shape)}",
            ))
    return out


@invariant("V304", name="head-io", scope="plan")
def _head_io(ctx):
    """The FC head, abstractly interpreted on the final feature shape,
    yields rank-2 float logits."""
    import jax
    import jax.numpy as jnp

    plan = ctx.plan
    last = plan.stages[-1].io
    if last is None:
        return []
    try:
        got = jax.eval_shape(
            plan.head_fn,
            jax.ShapeDtypeStruct((1,) + tuple(last.out_shape), jnp.float32),
        )
    except Exception as e:  # noqa: BLE001 — surfaced as a finding
        return [ctx.error(
            "V304",
            f"head fails to trace on the final feature shape "
            f"{tuple(last.out_shape)}: {e}",
        )]
    if len(got.shape) != 2 or got.shape[0] != 1:
        return [ctx.error(
            "V304",
            f"head produces shape {tuple(got.shape)}; expected rank-2 "
            "(batch, n_classes) logits",
        )]
    return []


@invariant("V306", name="blocks-intact", scope="plan")
def _blocks_intact(ctx):
    """No stage and no fusion group cuts a residual basic block: the
    layers of every block (a ``join`` layer and the ones before it) lie
    in one stage and one fusion group, so every skip edge stays inside
    one kernel and the stage graph stays a chain."""
    from repro.core.dhm.fusion import layer_units

    plan = ctx.plan
    layers = plan.topo.conv_layers
    out = []
    runs = [("stage", st.index, st.conv_layers) for st in plan.stages]
    runs += [
        ("fusion group", gi, g.layers)
        for gi, g in enumerate(plan.fusion_groups)
    ]
    for kind, i, run in runs:
        try:
            layer_units(layers, run)
        except ValueError as e:
            out.append(ctx.error("V306", f"{kind} {i}: {e}"))
    return out


@invariant("V305", name="serving-surface", scope="plan")
def _serving_surface(ctx):
    """The plan exposes the full surface the serving layer consumes —
    ``topo.input_shape``/``input_channels`` (frame geometry), callable
    ``features`` and ``head_fn``, and at least one stage — so a hot-swap
    target missing any of it is rejected by ``verify_plan`` instead of
    crashing the tenant's warmup dispatch."""
    plan = ctx.plan
    out = []
    topo = getattr(plan, "topo", None)
    shape = getattr(topo, "input_shape", None)
    if (
        topo is None
        or not isinstance(shape, (tuple, list))
        or len(shape) != 2
        or not isinstance(getattr(topo, "input_channels", None), int)
    ):
        out.append(ctx.error(
            "V305",
            "plan topology does not declare the serving frame geometry "
            "(input_shape pair + integer input_channels)",
        ))
    if not callable(getattr(plan, "features", None)):
        out.append(ctx.error(
            "V305", "plan has no callable ``features`` extractor"
        ))
    if not callable(getattr(plan, "head_fn", None)):
        out.append(ctx.error(
            "V305", "plan has no callable ``head_fn``"
        ))
    if not getattr(plan, "stages", ()):
        out.append(ctx.error("V305", "plan has no stages"))
    return out


# ---------------------------------------------------------------------------
# structure scope (V0xx)


@invariant("V001", name="one-dot-per-conv-layer", scope="structure")
def _one_dot_per_layer(ctx):
    """The traced feature extractor contains exactly one ``dot_general``
    per conv layer — the paper's one-MACC-array-per-actor mapping; a
    kernel that decomposes into per-tap matmuls (the seed's 25-dot
    lowering) fails here. A projection shortcut is a conv of its own and
    brings one more."""
    plan = ctx.plan
    if plan.backend not in _PALLAS_BACKENDS:
        return []
    n_conv = sum(len(st.conv_layers) for st in plan.stages)
    n_conv += sum(1 for p in plan.conv_params if "proj" in p)
    got = count_primitive(ctx.features_jaxpr(), "dot_general")
    if got != n_conv:
        return [ctx.error(
            "V001",
            f"feature trace has {got} dot_general eqns for {n_conv} convs "
            "(projections included) — expected exactly one per conv",
        )]
    return []


@invariant("V002", name="one-pallas-call-per-group", scope="structure")
def _one_pallas_call_per_group(ctx):
    """On the pallas path each fusion group lowers to exactly ONE fused
    kernel invocation (pallas_call) — the no-external-memory dataflow
    across fused layer boundaries."""
    plan = ctx.plan
    if plan.backend != _INTERPRET_BACKEND:
        return []  # pallas_call is only visible under the interpret probe
    n_groups = len(plan.fusion_groups)
    got = count_primitive(ctx.features_jaxpr(), "pallas_call")
    if got != n_groups:
        return [ctx.error(
            "V002",
            f"feature trace has {got} pallas_call eqns for {n_groups} "
            "fusion groups — expected exactly one per group",
        )]
    return []


@invariant("V003", name="no-conv-primitive", scope="structure")
def _no_conv_primitive(ctx):
    """No ``conv_general_dilated`` survives in the feature trace — the
    DHM lowering maps convolutions onto streamed matmuls, never onto
    XLA's im2col convolution."""
    plan = ctx.plan
    if plan.backend not in _PALLAS_BACKENDS:
        return []
    got = count_primitive(ctx.features_jaxpr(), "conv_general_dilated")
    if got:
        return [ctx.error(
            "V003",
            f"feature trace contains {got} conv_general_dilated eqn(s) — "
            "the plan fell back to XLA convolution",
        )]
    return []


@invariant("V004", name="dtype-drift", scope="structure")
def _dtype_drift(ctx):
    """All floating-point values in the end-to-end closure are float32
    (no float64/bfloat16 drift), and the logits are not weak-typed."""
    jaxpr = ctx.forward_jaxpr()
    out = []
    bad = sorted(
        {str(a.dtype) for a in float_avals(jaxpr) if str(a.dtype) != "float32"}
    )
    if bad:
        out.append(ctx.error(
            "V004",
            f"closure trace contains non-float32 float dtypes: {bad}",
        ))
    for var in jaxpr.jaxpr.outvars if hasattr(jaxpr, "jaxpr") else jaxpr.outvars:
        aval = getattr(var, "aval", None)
        if getattr(aval, "weak_type", False):
            out.append(ctx.error(
                "V004",
                "closure output is weak-typed — a python-scalar promotion "
                "leaked into the logits",
            ))
    return out


@invariant("V005", name="no-host-transfer", scope="structure")
def _no_host_transfer(ctx):
    """The jitted closure contains no host-transfer primitives
    (device_put / callbacks / infeed) — nothing may stall the serving
    hot path on a host round-trip."""
    seen = {}
    for eqn in iter_eqns(ctx.forward_jaxpr()):
        nm = eqn.primitive.name
        if nm in _HOST_TRANSFER_PRIMS:
            seen[nm] = seen.get(nm, 0) + 1
    if seen:
        return [ctx.error(
            "V005",
            f"closure trace contains host-transfer primitives: {seen}",
        )]
    return []


@invariant("V006", name="donation-declared", scope="structure")
def _donation_declared(ctx):
    """``jitted_forward(donate=True)`` really declares its input donation:
    either the lowering carries an aliasing/donation marker, or jax
    reports the donation unusable (input cannot alias the logits — still
    a declared donation). Neither signal means the donate flag was
    silently dropped."""
    text, warned = ctx.lower_donated()
    if text is None:
        return []  # plan has no jitted_forward(donate=) surface
    if "jax.buffer_donor" in text or "tf.aliasing_output" in text or warned:
        return []
    return [ctx.error(
        "V006",
        "donate=True produced neither an aliasing marker in the lowering "
        "nor an unusable-donation report — the donation was dropped",
    )]


@invariant("V007", name="in-kernel-stream-quant", scope="structure")
def _in_kernel_stream_quant(ctx):
    """With ``act_bits`` set, the feature-stream quantization rounds live
    INSIDE the fused kernels (one per conv layer), not as separate XLA
    ops between kernel calls — the paper quantizes the pixel flow inside
    the actor. Int8 plans additionally quantize each group's INPUT frame
    host-side (outside the kernel, so the resident frame is 1-byte codes
    — the V204 contract): exactly one extra round per fusion group is
    legal there, and no more."""
    plan = ctx.plan
    if plan.backend != _INTERPRET_BACKEND or plan.quant.act_bits is None:
        return []
    jaxpr = ctx.features_jaxpr()
    n_conv = sum(len(st.conv_layers) for st in plan.stages)
    inside = count_primitive_in_pallas(jaxpr, "round")
    total = count_primitive(jaxpr, "round")
    int8 = bool(getattr(plan.quant, "int8_compute", False))
    allowed_outside = len(plan.fusion_groups) if int8 else 0
    out = []
    if inside != n_conv:
        out.append(ctx.error(
            "V007",
            f"{inside} in-kernel stream-quant round(s) for {n_conv} conv "
            "layers — expected one per layer inside the pallas bodies",
        ))
    if total - inside != allowed_outside:
        out.append(ctx.error(
            "V007",
            f"{total - inside} stream-quant round(s) outside the kernels "
            f"in the XLA graph — expected {allowed_outside} "
            f"({'one input-quantize per fusion group' if int8 else 'none'})",
        ))
    return out


@invariant("V008", name="integer-conv-compute", scope="structure")
def _integer_conv_compute(ctx):
    """An ``int8_compute`` plan really computes in integers: every conv
    contraction in the feature trace takes integer operands and
    accumulates into an int32 result (``preferred_element_type``) — no
    hidden decode-to-fp32 matmul before the requantizing epilogue."""
    plan = ctx.plan
    if plan.backend not in _PALLAS_BACKENDS:
        return []
    if not bool(getattr(plan.quant, "int8_compute", False)):
        return []
    out = []
    dots = find_primitive(ctx.features_jaxpr(), "dot_general")
    if not dots:
        return [ctx.error(
            "V008", "int8 plan's feature trace contains no dot_general eqns"
        )]
    import jax.numpy as jnp

    for di, eqn in enumerate(dots):
        in_dtypes = [getattr(v.aval, "dtype", None) for v in eqn.invars]
        if not all(
            d is not None and jnp.issubdtype(d, jnp.integer) for d in in_dtypes
        ):
            out.append(ctx.error(
                "V008",
                f"dot_general #{di} takes {[str(d) for d in in_dtypes]} "
                "operands — an int8 plan upcast to float before the matmul",
            ))
            continue
        out_dtype = getattr(eqn.outvars[0].aval, "dtype", None)
        if out_dtype != jnp.int32:
            out.append(ctx.error(
                "V008",
                f"dot_general #{di} accumulates into {out_dtype} — int8 "
                "contractions must accumulate into int32 "
                "(preferred_element_type)",
            ))
    return out


# ---------------------------------------------------------------------------
# resource scope (V2xx)


@invariant("V201", name="group-budget", scope="resource")
def _group_budget(ctx):
    """Every fusion group's costed working set fits the plan's VMEM
    budget (skipped for budget-0 per-layer lowerings, whose single-layer
    groups are emitted unconditionally)."""
    plan = ctx.plan
    if plan.vmem_budget <= 0:
        return []
    out = []
    for gi, g in enumerate(plan.fusion_groups):
        if g.working_set > plan.vmem_budget:
            out.append(ctx.error(
                "V201",
                f"fusion group {gi} (layers {tuple(g.layers)}) working set "
                f"{g.working_set} B exceeds the vmem budget "
                f"{plan.vmem_budget} B",
            ))
    return out


@invariant("V202", name="cost-model-consistent", scope="resource")
def _cost_model_consistent(ctx):
    """Each fused group's recorded working set equals what ``fusion.py``
    / ``halo.py`` cost today for the same layers and block_rows — a
    stale or hand-edited plan cannot smuggle in an outdated cost. A
    single-layer group records none (its kernel is row-blocked, not a
    pyramid) and is not re-costed."""
    from repro.core.dhm.fusion import (
        group_working_set,
        group_working_set_breakdown,
        plan_elem_bytes,
    )

    plan = ctx.plan
    elem_bytes = plan_elem_bytes(plan.quant)
    out = []
    for gi, g in enumerate(plan.fusion_groups):
        if not g.fused:
            continue  # a single-layer kernel: no pyramid working set
        try:
            want = group_working_set(
                plan.topo, g.layers, block_rows=g.block_rows,
                elem_bytes=elem_bytes,
            )
        except Exception as e:  # noqa: BLE001 — surfaced as a finding
            out.append(ctx.error(
                "V202",
                f"fusion group {gi} (layers {tuple(g.layers)}) cannot be "
                f"re-costed: {e}",
            ))
            continue
        if want != g.working_set:
            parts = group_working_set_breakdown(
                plan.topo, g.layers, block_rows=g.block_rows,
                elem_bytes=elem_bytes,
            )
            top = max(parts, key=parts.get)
            out.append(ctx.error(
                "V202",
                f"fusion group {gi} (layers {tuple(g.layers)}) records a "
                f"working set of {g.working_set} B but the cost model says "
                f"{want} B at {elem_bytes} B/elt (largest component: {top} "
                f"= {parts[top]} B)",
            ))
    return out


@invariant("V203", name="traced-working-set", scope="resource")
def _traced_working_set(ctx):
    """The traced per-kernel footprint (pallas_call operand avals + the
    widest body intermediate) stays under the group's costed working set
    — a planner under-estimate surfaces here, not as a Mosaic OOM."""
    plan = ctx.plan
    if plan.backend != _INTERPRET_BACKEND:
        return []
    calls = find_primitive(ctx.features_jaxpr(), "pallas_call")
    groups = plan.fusion_groups
    if len(calls) != len(groups):
        return []  # V002 reports the mismatch
    out = []
    for gi, (eqn, g) in enumerate(zip(calls, groups)):
        if not g.fused:
            continue  # a single-layer kernel: no pyramid working set
        operands = sum(aval_bytes(v.aval) for v in eqn.invars)
        widest = 0
        for sub in iter_eqns(eqn.params.get("jaxpr", [])):
            for var in sub.outvars:
                widest = max(widest, aval_bytes(getattr(var, "aval", None)))
        bound = operands + widest
        if bound > g.working_set:
            out.append(ctx.error(
                "V203",
                f"fusion group {gi} (layers {tuple(g.layers)}): traced "
                f"footprint lower bound {bound} B (operands {operands} + "
                f"widest intermediate {widest}) exceeds the costed working "
                f"set {g.working_set} B — the planner under-estimated",
            ))
    return out


@invariant("V204", name="int8-slab-costing", scope="resource")
def _int8_slab_costing(ctx):
    """An int8 plan charges int8 slab bytes (1 B/elt for the resident
    frame, feature slabs and weight codes; int32 accumulators stay 4 B)
    against ``vmem_budget`` — the recorded working sets must equal the
    int8 costing and, for multi-layer groups, be strictly below what the
    same group costs at fp32. A plan that books fp32 bytes under an int8
    contract wastes the budget headroom the 1-byte slabs buy."""
    from repro.core.dhm.fusion import group_working_set, plan_elem_bytes

    plan = ctx.plan
    if plan_elem_bytes(plan.quant) != 1:
        return []
    out = []
    for gi, g in enumerate(plan.fusion_groups):
        if not g.fused:
            continue  # a single-layer kernel: no pyramid working set
        try:
            want_int8 = group_working_set(
                plan.topo, g.layers, block_rows=g.block_rows, elem_bytes=1
            )
            want_fp32 = group_working_set(
                plan.topo, g.layers, block_rows=g.block_rows, elem_bytes=4
            )
        except Exception as e:  # noqa: BLE001 — surfaced as a finding
            out.append(ctx.error(
                "V204",
                f"fusion group {gi} (layers {tuple(g.layers)}) cannot be "
                f"re-costed: {e}",
            ))
            continue
        if g.working_set != want_int8:
            out.append(ctx.error(
                "V204",
                f"fusion group {gi} (layers {tuple(g.layers)}) records "
                f"{g.working_set} B under an int8 plan; the int8 costing "
                f"says {want_int8} B",
            ))
        elif g.working_set >= want_fp32:
            out.append(ctx.error(
                "V204",
                f"fusion group {gi} (layers {tuple(g.layers)}) int8 "
                f"working set {g.working_set} B is not below the fp32 "
                f"costing {want_fp32} B — int8 slabs bought no headroom",
            ))
    return out


# ---------------------------------------------------------------------------
# pipeline scope (V1xx)


def _ppermute_by_class(ctx):
    """Map each traced ppermute eqn to its EdgePlan shape class by perm
    identity; returns (assignments, unmatched_eqns)."""
    probe = ctx.pipeline
    calls = find_primitive(probe.jaxpr, "ppermute")
    pairs_of = {
        c: frozenset(map(tuple, probe.edge_plan.class_pairs(c)))
        for c in range(probe.edge_plan.n_classes)
    }
    assigned, unmatched = [], []
    for eqn in calls:
        perm = frozenset(map(tuple, eqn.params.get("perm", ())))
        for c, pairs in pairs_of.items():
            if perm == pairs:
                assigned.append((c, eqn))
                break
        else:
            unmatched.append(eqn)
    return assigned, unmatched


@invariant("V101", name="interior-edge-count", scope="pipeline")
def _interior_edge_count(ctx):
    """The traced pipelined closure contains exactly the EdgePlan's
    collectives: one ppermute per shape class, whose perms together cover
    every interior edge (s, s+1) exactly once — S-1 edges total."""
    probe = ctx.pipeline
    if probe is None:
        return []
    ep = probe.edge_plan
    if ep.n_edges == 0:
        return []
    assigned, unmatched = _ppermute_by_class(ctx)
    out = []
    if unmatched:
        perms = [sorted(e.params.get("perm", ())) for e in unmatched]
        out.append(ctx.error(
            "V101",
            f"{len(unmatched)} traced ppermute(s) match no EdgePlan shape "
            f"class: perms {perms}",
        ))
    got_classes = sorted(c for c, _ in assigned)
    want_classes = list(range(ep.n_classes))
    if got_classes != want_classes:
        out.append(ctx.error(
            "V101",
            f"traced collectives cover shape classes {got_classes}; the "
            f"EdgePlan requires exactly one ppermute per class "
            f"{want_classes}",
        ))
        return out
    covered = set()
    for c, _ in assigned:
        covered.update(map(tuple, ep.class_pairs(c)))
    want = {(s, s + 1) for s in range(ep.n_edges)}
    if covered != want:
        out.append(ctx.error(
            "V101",
            f"traced ppermutes cover interior edges {sorted(covered)}; the "
            f"plan has {ep.n_edges} interior edges {sorted(want)}",
        ))
    return out


@invariant("V102", name="edge-exact-shape", scope="pipeline")
def _edge_exact_shape(ctx):
    """Each class's ppermute moves exactly (microbatch, *class_shape)
    elements — no silently widened (padded) transfer on the exact path."""
    probe = ctx.pipeline
    if probe is None or probe.edge_plan.n_edges == 0:
        return []
    assigned, _ = _ppermute_by_class(ctx)
    out = []
    for c, eqn in assigned:
        want = (probe.mb_local,) + tuple(probe.edge_plan.class_shapes[c])
        got = tuple(eqn.invars[0].aval.shape)
        if got != want:
            out.append(ctx.error(
                "V102",
                f"shape class {c} ppermute moves {got}; the EdgePlan "
                f"promises exactly {want}",
            ))
    return out


@invariant("V103", name="boxed-padding", scope="pipeline")
def _boxed_padding(ctx):
    """A boxed (max-shape) edge fallback is legal but pays padding bytes
    on every hop — flag it with the fraction so the regression is
    visible, not silent."""
    probe = ctx.pipeline
    if probe is None or probe.edge_plan.mode != "boxed":
        return []
    frac = probe.edge_plan.padding_fraction()
    return [ctx.warning(
        "V103",
        f"edge plan fell back to boxed transfers: "
        f"{frac:.1%} of every interior-edge hop is padding",
    )]
