"""Streaming pipelined executor: the TPU incarnation of DHM's "all actors
always firing" model, for *heterogeneous* stage geometries.

Stages are assigned to disjoint device groups along a mesh axis
(``stage``). Each device group keeps its stage's parameters resident
(private resources, as in DHM) and processes a stream of µbatches; the
activation stream flows stage -> stage+1 over ICI via
``jax.lax.ppermute`` — the edge of the dataflow graph becomes a physical
link, never touching host or "external" memory.

Schedule: GPipe fill/steady/drain. For M µbatches and S stages the loop
runs T = M + S - 1 ticks; at tick t stage s processes µbatch (t - s) when
0 <= t - s < M. All stages fire every tick (fill/drain ticks process
garbage that is masked out) — matching the paper's fully-pipelined,
always-firing actors.

Real CNN topologies pool/stride down and grow channels between stages, so
stage bodies are NOT shape-homogeneous. The executor sizes the ICI stream
to the actual tensor traffic: the interior edge shapes (stage s ->
stage s+1, from the compiler's :class:`StageIOSpec` chain) are grouped
into **shape classes** (:func:`plan_edges`). Each class gets its own
in-flight buffer and its own *partial* ``ppermute`` — only the devices
whose out-edge belongs to the class appear as sources, so every edge
moves exactly its own bytes (the stage-0 input and the final output never
travel over ICI and never inflate a buffer). When every class holds edges
of a single shape the stream is **exact** (zero padding, zero slack —
the default, taken by every real topology); collapsing all edges into one
max-shape class is the **boxed** general fallback
(``PipelineConfig.edge_mode="boxed"``), numerics untouched either way.
Since each device executes one stage, the per-stage bodies are selected
with ``lax.switch`` on the device's stage index — one SPMD program, S
different actor chains. Parameters are boxed the old way (leaf-wise
pad-to-max, stacked on a leading stage axis) so each device group holds
exactly its own stage's weights.

With ``PipelineConfig.overlap=True`` the edge slots are double-buffered:
the scan carry holds separate in-flight *send* and *recv* slots, so the
``ppermute`` of µbatch m (launched from the send slot filled last tick)
is independent of — and overlaps with — the ``lax.switch`` stage body of
µbatch m+1 in the same tick. Each edge then costs one extra pipeline tick
(T = M + 2(S-1) instead of M + S - 1), the classic latency-for-bandwidth
trade: worth it when collectives run asynchronously beside compute (real
ICI), not on an emulated host mesh — the µbatch autotuner
(``repro.core.dhm.throughput``) decides from measured sweeps. Both
schedules compute bit-identical outputs.

A 2D ``(stage, data)`` mesh composes data-parallel batch sharding with the
spatial pipeline: the µbatch dimension is sharded along ``data_axis`` and
each data column runs an independent pipeline over its batch shard.

Stage bodies emitted by the compiler (``emit_conv_stage``) fuse a stage's
layer run into cross-layer pyramid groups under the VMEM budget — the
stage then executes as one (or a few) ``stream_conv_pyramid`` kernel calls
and only stage boundaries remain activation-streaming edges over ICI.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P


class CollectiveTimeout(RuntimeError):
    """A dispatched step (typically the mesh collective runner) failed to
    complete within its deadline — the runtime analogue of a wedged
    ``ppermute``. The serving engine treats this as a rung-level failure
    and demotes (mesh pipeline -> single device) instead of hanging."""


def call_with_timeout(fn: Callable, *, timeout_s: Optional[float], what: str = "dispatch"):
    """Run ``fn()`` (which must block until its result is ready) under a
    watchdog: if it does not return within ``timeout_s``, raise
    :class:`CollectiveTimeout` — the caller regains control even though
    the wedged computation cannot be cancelled (the worker thread is
    abandoned as a daemon and its eventual result discarded).

    ``timeout_s=None`` (or <= 0) runs ``fn`` inline with no watchdog.
    This is the timeout hook the serving engine wraps around every
    dispatch — most importantly the collective runner, where a lost peer
    stalls the whole mesh instead of raising.
    """
    if not timeout_s or timeout_s <= 0:
        return fn()
    box: dict = {}
    done = threading.Event()

    def _run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_run, daemon=True, name=f"watchdog-{what}")
    t.start()
    if not done.wait(timeout_s):
        raise CollectiveTimeout(
            f"{what} did not complete within {timeout_s:.3f}s"
        )
    if "error" in box:
        raise box["error"]
    return box["value"]


@dataclasses.dataclass(frozen=True)
class StageIOSpec:
    """Static activation geometry of one pipeline stage: the per-µbatch
    element shape entering and leaving the stage (without the µbatch
    dimension — e.g. ``(H, W, C)`` for conv stages). Consecutive stages
    must chain: ``io[s].out_shape == io[s + 1].in_shape``."""

    in_shape: tuple
    out_shape: tuple

    def __post_init__(self):
        for name in ("in_shape", "out_shape"):
            shp = getattr(self, name)
            if not all(isinstance(d, int) and d >= 1 for d in shp):
                raise ValueError(
                    f"StageIOSpec.{name} must be positive ints, got {shp!r}"
                )


EDGE_MODES = ("auto", "exact", "boxed")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    stage_axis: str = "stage"
    data_axis: Optional[str] = None  # optional batch-sharding mesh axis
    # How interior-edge activations travel over ICI (see plan_edges):
    # "auto" sends exact-shape per-class buffers, collapsing to one boxed
    # class only past max_edge_classes; "exact" never collapses; "boxed"
    # forces the single max-shape box (the general fallback).
    edge_mode: str = "auto"
    max_edge_classes: int = 4
    # Double-buffer the edge slots so the ppermute of µbatch m overlaps
    # the stage body of µbatch m+1 (one extra tick of latency per edge).
    overlap: bool = False

    def __post_init__(self):
        if self.n_microbatches < 1 or self.n_stages < 1:
            raise ValueError("n_stages and n_microbatches must be >= 1")
        if self.data_axis is not None and self.data_axis == self.stage_axis:
            raise ValueError("data_axis must differ from stage_axis")
        if self.edge_mode not in EDGE_MODES:
            raise ValueError(
                f"unknown edge_mode {self.edge_mode!r}; expected one of "
                f"{EDGE_MODES}"
            )
        if self.max_edge_classes < 1:
            raise ValueError("max_edge_classes must be >= 1")


# ---------------------------------------------------------------------------
# Boxing: embed heterogeneous shapes in one max-shape buffer.


def _aligned(shape: tuple, rank: int) -> tuple:
    """Rank-align a shape by prepending singleton dims."""
    return (1,) * (rank - len(shape)) + tuple(shape)


def _box_of(shapes: Sequence[tuple]) -> tuple:
    """The elementwise-max box that embeds every (rank-aligned) shape."""
    rank = max(len(s) for s in shapes)
    return tuple(max(dims) for dims in zip(*(_aligned(s, rank) for s in shapes)))


def _fit(a: jax.Array, box: tuple) -> jax.Array:
    """Zero-pad ``a`` (rank-aligned) into the box shape."""
    a = a.reshape(_aligned(a.shape, len(box)))
    return jnp.pad(a, [(0, b - d) for d, b in zip(a.shape, box)])


def _unfit(a_box: jax.Array, shape: tuple) -> jax.Array:
    """Slice the true ``shape`` back out of a boxed array (inverse of
    :func:`_fit` — exact, no numerics touched)."""
    idx = tuple(slice(0, d) for d in _aligned(shape, a_box.ndim))
    return a_box[idx].reshape(shape)


def _fit_elem(y: jax.Array, class_shape: tuple) -> jax.Array:
    """Zero-pad a (mb, *elem) activation into (mb, *class_shape) — the
    element dims are rank-aligned AFTER the µbatch dim. Pad-free (a pure
    reshape) when the class shape equals the element shape, i.e. on every
    exact-shape edge."""
    el = _aligned(y.shape[1:], len(class_shape))
    y = y.reshape((y.shape[0],) + el)
    pad = [(0, 0)] + [(0, b - d) for d, b in zip(el, class_shape)]
    if all(p == (0, 0) for p in pad):
        return y
    return jnp.pad(y, pad)


def _unfit_elem(y_box: jax.Array, shape: tuple) -> jax.Array:
    """Slice the true (mb, *shape) activation back out of a class buffer
    (inverse of :func:`_fit_elem` — exact, no numerics touched)."""
    idx = (slice(None),) + tuple(
        slice(0, d) for d in _aligned(shape, y_box.ndim - 1)
    )
    return y_box[idx].reshape((y_box.shape[0],) + tuple(shape))


# ---------------------------------------------------------------------------
# Edge planning: size the ICI stream to the actual tensor traffic.


@dataclasses.dataclass(frozen=True)
class EdgePlan:
    """How stage-boundary activations travel over ICI.

    The pipeline's S-1 *interior* edges (stage s -> s+1; the stage-0 input
    and final output never cross ICI) are grouped into shape classes. Each
    class owns one in-flight buffer of ``class_shapes[c]`` and one partial
    ``ppermute`` whose pairs are exactly the class's edges — devices whose
    out-edge is in another class send nothing, so per-tick edge traffic is
    the sum of the true edge payloads, not S-1 copies of the global max
    box.

    ``mode`` is ``"exact"`` when every class holds edges of one shape
    (class buffers carry zero padding — the fast path every chain-CNN
    topology takes) and ``"boxed"`` when classes were collapsed into a
    max-shape box (the general fallback, numerics identical).
    """

    mode: str
    edge_shapes: tuple  # per interior edge: the exact element shape
    class_shapes: tuple  # per class: the (rank-aligned) buffer elem shape
    edge_class: tuple  # per interior edge: index into class_shapes

    @property
    def n_edges(self) -> int:
        return len(self.edge_shapes)

    @property
    def n_classes(self) -> int:
        return len(self.class_shapes)

    def class_pairs(self, c: int) -> list:
        """The ``ppermute`` permutation of class ``c``: (s, s+1) for every
        stage s whose out-edge belongs to the class."""
        return [
            (e, e + 1) for e in range(self.n_edges) if self.edge_class[e] == c
        ]

    def class_bytes(self, itemsize: int = 4) -> tuple:
        """Per-class buffer bytes for one element (no µbatch dim)."""
        out = []
        for cs in self.class_shapes:
            n = 1
            for d in cs:
                n *= d
            out.append(n * itemsize)
        return tuple(out)

    def padding_fraction(self, itemsize: int = 4) -> float:
        """Fraction of the per-tick ICI traffic that is zero padding
        (0.0 on the exact path)."""
        sent = sum(
            self.class_bytes(itemsize)[self.edge_class[e]]
            for e in range(self.n_edges)
        )
        true = sum(
            itemsize * _prod(self.edge_shapes[e]) for e in range(self.n_edges)
        )
        return 1.0 - true / sent if sent else 0.0


def _prod(shape: tuple) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def plan_edges(
    io_specs: Sequence[StageIOSpec],
    *,
    mode: str = "auto",
    max_classes: int = 4,
) -> EdgePlan:
    """Group the pipeline's interior edge shapes into ICI shape classes.

    ``mode="auto"`` emits one class per distinct (rank-aligned) edge shape
    — the exact-shape stream — collapsing everything into a single
    max-shape box only when that would exceed ``max_classes`` in-flight
    buffers; ``"exact"`` never collapses; ``"boxed"`` always does (the
    general fallback the boxed executor used for every topology).
    """
    if mode not in EDGE_MODES:
        raise ValueError(
            f"unknown edge mode {mode!r}; expected one of {EDGE_MODES}"
        )
    io_specs = tuple(io_specs)
    edges = tuple(
        tuple(io_specs[s].out_shape) for s in range(len(io_specs) - 1)
    )
    if not edges:
        return EdgePlan(
            mode="exact", edge_shapes=(), class_shapes=(), edge_class=()
        )
    rank = max(len(e) for e in edges)
    aligned = [_aligned(e, rank) for e in edges]
    distinct = []
    for a in aligned:
        if a not in distinct:
            distinct.append(a)
    if mode == "boxed" or (mode == "auto" and len(distinct) > max_classes):
        return EdgePlan(
            mode="boxed",
            edge_shapes=edges,
            class_shapes=(_box_of(edges),),
            edge_class=(0,) * len(edges),
        )
    return EdgePlan(
        mode="exact",
        edge_shapes=edges,
        class_shapes=tuple(distinct),
        edge_class=tuple(distinct.index(a) for a in aligned),
    )


def _box_stage_params(per_stage_params: Sequence):
    """Box heterogeneous per-stage param pytrees into stackable leaves.

    Returns ``(stacked, meta)`` where ``stacked`` is a list of
    ``(S, *box)`` arrays (leaf slot i of every stage, padded to the slot's
    max shape; stages with fewer leaves contribute zeros) and ``meta``
    carries the static per-stage treedefs / leaf shapes / dtypes needed to
    reconstruct each stage's exact params inside its branch.
    """
    flat = [jax.tree_util.tree_flatten(p) for p in per_stage_params]
    leaves = [[jnp.asarray(x) for x in l] for l, _ in flat]
    treedefs = [td for _, td in flat]
    n_slots = max(len(l) for l in leaves)
    boxes, box_dtypes = [], []
    for i in range(n_slots):
        slot = [l[i] for l in leaves if len(l) > i]
        boxes.append(_box_of([x.shape for x in slot]))
        box_dtypes.append(jnp.result_type(*[x.dtype for x in slot]))
    stacked = []
    for i in range(n_slots):
        stacked.append(
            jnp.stack(
                [
                    _fit(l[i].astype(box_dtypes[i]), boxes[i])
                    if len(l) > i
                    else jnp.zeros(boxes[i], box_dtypes[i])
                    for l in leaves
                ]
            )
        )
    meta = {
        "treedefs": treedefs,
        "shapes": [[x.shape for x in l] for l in leaves],
        "dtypes": [[x.dtype for x in l] for l in leaves],
    }
    return stacked, meta


def derive_io_specs(
    stage_fns: Sequence[Callable], per_stage_params: Sequence, in_shape: tuple
) -> tuple:
    """Chain ``jax.eval_shape`` through the stage bodies to recover every
    boundary's activation geometry (used when the caller has no compiler
    plan to emit :class:`StageIOSpec` from)."""
    specs = []
    shape = tuple(in_shape)
    for fn, params in zip(stage_fns, per_stage_params):
        out = jax.eval_shape(
            fn, params, jax.ShapeDtypeStruct((1,) + shape, jnp.float32)
        )
        specs.append(StageIOSpec(in_shape=shape, out_shape=tuple(out.shape[1:])))
        shape = tuple(out.shape[1:])
    return tuple(specs)


def _validate_io_chain(io_specs: Sequence[StageIOSpec]):
    for s in range(len(io_specs) - 1):
        if tuple(io_specs[s].out_shape) != tuple(io_specs[s + 1].in_shape):
            raise ValueError(
                f"stage {s} output {tuple(io_specs[s].out_shape)} does not "
                f"chain into stage {s + 1} input "
                f"{tuple(io_specs[s + 1].in_shape)}"
            )


# ---------------------------------------------------------------------------
# The executor.


def make_pipeline_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """The mesh the spatial pipeline runs on: ``jax.make_mesh`` with every
    axis ``Auto``. JAX makes new mesh axes ``Explicit`` by default, and
    the executor's stage selection and param placement rely on the
    compiler choosing shardings (``Auto``); every caller builds its stage
    and ``(stage, data)`` meshes here. ``devices`` defaults to
    ``jax.devices()``."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


@dataclasses.dataclass(frozen=True)
class PipelinedRunner:
    """A built spatial pipeline: the shard_map'd GPipe executor plus the
    boxed per-stage parameter leaves, stacked ONCE at build time (eagerly,
    outside any enclosing ``jit`` trace) and laid out so each stage's
    device group holds exactly its own weights (DHM's private
    resources).

    ``runner(microbatches)`` runs the resident leaves; ``runner.apply``
    is the pure ``(leaves, microbatches) -> outputs`` function for
    composing under ``jit`` with the leaves passed as arguments (the
    serving ``Engine``'s path).
    """

    cfg: PipelineConfig
    io_specs: tuple
    edge_plan: EdgePlan  # how interior edges travel over ICI (see plan_edges)
    stacked_leaves: list  # (S, *box) per leaf slot, sharded P(stage_axis)
    _apply: Callable

    def apply(self, leaves, microbatches: jax.Array) -> jax.Array:
        """Pure executor: (stacked leaves, (M, mb, *elem) µbatches) ->
        (M, mb, *out_elem) final-stage outputs."""
        return self._apply(leaves, microbatches)

    def apply_with_timeout(
        self, leaves, microbatches: jax.Array, *, timeout_s: Optional[float]
    ) -> jax.Array:
        """:meth:`apply` under the :func:`call_with_timeout` watchdog,
        blocked until ready — raises :class:`CollectiveTimeout` instead of
        hanging when the mesh collective wedges (the serving engine's
        demotion hook)."""

        def _run():
            out = self._apply(leaves, microbatches)
            return jax.block_until_ready(out)

        return call_with_timeout(
            _run, timeout_s=timeout_s, what="pipelined collective"
        )

    def __call__(self, microbatches: jax.Array) -> jax.Array:
        return self._apply(self.stacked_leaves, microbatches)


def build_pipeline(
    stage_fns: Sequence[Callable],
    stage_params: Sequence,
    *,
    mesh: jax.sharding.Mesh,
    cfg: PipelineConfig,
    io_specs: Optional[Sequence[StageIOSpec]] = None,
    microbatch: Optional[int] = None,
    dtype=jnp.float32,
) -> PipelinedRunner:
    """Build the heterogeneous spatial pipeline once: validate the edge
    geometry, box + stack the per-stage params (eagerly), and close the
    shard_map'd fill/steady/drain executor over the static metadata.

    Args:
      stage_fns: S per-stage callables ``(params_s, x) -> y``; shapes may
        differ per boundary (pool/stride shrink, channel growth).
      stage_params: per-stage param pytrees (structure and leaf shapes
        may differ per stage).
      mesh: mesh containing ``cfg.stage_axis`` (and ``cfg.data_axis``).
      io_specs: per-stage :class:`StageIOSpec` (the compiler emits these
        from the topology's geometry; :func:`derive_io_specs` recovers
        them from the stage bodies when no plan is at hand). Required.
      microbatch: µbatch size (for the data-axis divisibility check at
        build time; otherwise checked at call time).
      dtype: dtype of the boxed activation stream.
    """
    S, M = cfg.n_stages, cfg.n_microbatches
    ax = cfg.stage_axis
    explicit = [
        a for a, t in zip(mesh.axis_names, mesh.axis_types)
        if t != AxisType.Auto
    ]
    if explicit:
        raise ValueError(
            f"mesh axes {explicit} are not Auto; build pipeline meshes "
            "with make_pipeline_mesh"
        )
    if mesh.shape[ax] != S:
        raise ValueError(
            f"mesh axis {ax!r} has {mesh.shape[ax]} devices, need {S}"
        )
    D = 1
    if cfg.data_axis is not None:
        D = mesh.shape[cfg.data_axis]
        if microbatch is not None and microbatch % D:
            raise ValueError(
                f"µbatch size {microbatch} not divisible by data axis "
                f"{cfg.data_axis!r} ({D} devices)"
            )
    stage_fns = list(stage_fns)
    if len(stage_fns) != S:
        raise ValueError(f"got {len(stage_fns)} stage fns for {S} stages")
    stage_params = list(stage_params)
    if len(stage_params) != S:
        raise ValueError(
            f"got {len(stage_params)} per-stage param trees for {S} stages"
        )

    if io_specs is None:
        raise ValueError(
            "build_pipeline needs io_specs (or use pipeline_forward, which "
            "derives them from the µbatch stream)"
        )
    io_specs = tuple(io_specs)
    if len(io_specs) != S:
        raise ValueError(f"got {len(io_specs)} io specs for {S} stages")
    _validate_io_chain(io_specs)

    # Size the ICI stream to the actual tensor traffic: group the S-1
    # interior edges into shape classes (stage-0 input and final output
    # never travel over ICI, so they inflate no buffer).
    edge_plan = plan_edges(
        io_specs, mode=cfg.edge_mode, max_classes=cfg.max_edge_classes
    )
    class_pairs = [edge_plan.class_pairs(c) for c in range(edge_plan.n_classes)]
    elem_shape = tuple(io_specs[0].in_shape)
    out_elem = tuple(io_specs[-1].out_shape)
    box_dtype = dtype

    stacked_leaves, meta = _box_stage_params(stage_params)
    # Each stage's device group keeps its own (boxed) weights resident.
    sharding = jax.sharding.NamedSharding(mesh, P(ax))
    stacked_leaves = [jax.device_put(l, sharding) for l in stacked_leaves]

    # Each edge adds one tick of pipeline delay per hop; the overlapped
    # schedule double-buffers every hop (send slot this tick, ppermute
    # next tick), doubling the fill/drain delay in exchange for making the
    # collective independent of the same-tick stage body.
    delay = (2 if cfg.overlap else 1) * (S - 1)
    n_ticks = M + delay

    def _per_stage(leaves, mb_stream):
        # Inside shard_map: each boxed leaf has leading dim 1 (this stage's
        # slice); mb_stream is this data column's (M, mb_local, *elem).
        local = [l[0] for l in leaves]
        mb_local = mb_stream.shape[1]
        stage_id = jax.lax.axis_index(ax)
        slot_shapes = [
            (mb_local,) + tuple(cs) for cs in edge_plan.class_shapes
        ]

        def make_branch(s):
            shapes_s = meta["shapes"][s]
            dtypes_s = meta["dtypes"][s]

            def branch(operand):
                x0, recv, lv_box = operand
                lv = [
                    _unfit(lv_box[i], shapes_s[i]).astype(dtypes_s[i])
                    for i in range(len(shapes_s))
                ]
                params = jax.tree_util.tree_unflatten(meta["treedefs"][s], lv)
                if s == 0:
                    x = x0  # injected directly; never crossed ICI
                else:
                    x = _unfit_elem(
                        recv[edge_plan.edge_class[s - 1]],
                        io_specs[s].in_shape,
                    )
                y = stage_fns[s](params, x)
                want = (mb_local,) + tuple(io_specs[s].out_shape)
                if tuple(y.shape) != want:
                    raise ValueError(
                        f"stage {s} produced {tuple(y.shape)}, but its "
                        f"StageIOSpec promises {want}"
                    )
                y = y.astype(box_dtype)
                # Every branch returns identical avals: one send slot per
                # class (this stage fills only its own out-edge's class)
                # and the exact final-edge output (zeros off-final).
                sends = tuple(
                    _fit_elem(y, edge_plan.class_shapes[c])
                    if s < S - 1 and edge_plan.edge_class[s] == c
                    else jnp.zeros(slot_shapes[c], box_dtype)
                    for c in range(edge_plan.n_classes)
                )
                out = (
                    y
                    if s == S - 1
                    else jnp.zeros((mb_local,) + out_elem, box_dtype)
                )
                return sends, out

            return branch

        branches = [make_branch(s) for s in range(S)]
        zero_slots = tuple(
            jnp.zeros(shp, box_dtype) for shp in slot_shapes
        )
        out_buf0 = jnp.zeros((M, mb_local) + out_elem, box_dtype)

        def shift(slots):
            # One ICI hop per shape class: partial permutation — only the
            # stages whose out-edge is in the class send; everyone else's
            # slot arrives as zeros (ppermute semantics).
            return tuple(
                jax.lax.ppermute(slots[c], ax, class_pairs[c])
                for c in range(edge_plan.n_classes)
            )

        def write_out(out_buf, out, t):
            # µbatch index this stage just finished; masked fill/drain.
            mb_idx = t - (2 if cfg.overlap else 1) * stage_id
            valid = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            slot = jnp.clip(mb_idx, 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(
                out_buf, slot, axis=0, keepdims=False
            )
            return jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(valid, out, prev), slot, axis=0
            )

        def inject(t):
            # Stage 0 injects µbatch t (zeros once the stream is drained).
            i = jnp.where(t < M, t, 0)
            x0 = jax.lax.dynamic_index_in_dim(
                mb_stream, i, axis=0, keepdims=False
            )
            return x0.astype(box_dtype)

        if cfg.overlap:

            def tick(carry, t):
                recv, send, out_buf = carry
                # The hop of last tick's send slot is data-independent of
                # this tick's switch body — XLA overlaps them.
                new_recv = shift(send)
                sends, out = jax.lax.switch(
                    stage_id, branches, (inject(t), recv, local)
                )
                return (new_recv, sends, write_out(out_buf, out, t)), None

            carry0 = (zero_slots, zero_slots, out_buf0)
            (_, _, out_buf), _ = jax.lax.scan(
                tick, carry0, jnp.arange(n_ticks)
            )
        else:

            def tick(carry, t):
                recv, out_buf = carry
                sends, out = jax.lax.switch(
                    stage_id, branches, (inject(t), recv, local)
                )
                return (shift(sends), write_out(out_buf, out, t)), None

            (_, out_buf), _ = jax.lax.scan(
                tick, (zero_slots, out_buf0), jnp.arange(n_ticks)
            )
        # Leading singleton stage axis so out_specs can shard it.
        return out_buf[None]

    dax = cfg.data_axis
    in_specs = (
        [P(ax) for _ in stacked_leaves],
        P(None, dax) if dax else P(),  # µbatch stream (only stage 0 reads it)
    )
    out_specs = P(ax, None, dax) if dax else P(ax)
    shmap = jax.shard_map(
        _per_stage, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

    def _apply(leaves, microbatches):
        if microbatches.shape[0] != M:
            raise ValueError(
                f"expected {M} microbatches, got {microbatches.shape[0]}"
            )
        if tuple(microbatches.shape[2:]) != elem_shape:
            raise ValueError(
                f"µbatch element shape {tuple(microbatches.shape[2:])} does "
                f"not match stage 0 input {elem_shape}"
            )
        mb = microbatches.shape[1]
        if mb % D:
            raise ValueError(
                f"µbatch size {mb} not divisible by data axis "
                f"{cfg.data_axis!r} ({D} devices)"
            )
        stacked = shmap(leaves, microbatches)  # (S, M, mb, *out_elem)
        # Output buffers are exact-shape; only stage S-1 wrote real values.
        return stacked[-1]

    return PipelinedRunner(
        cfg=cfg, io_specs=io_specs, edge_plan=edge_plan,
        stacked_leaves=stacked_leaves, _apply=_apply,
    )


def pipeline_forward(
    stage_fn,
    stage_params,
    microbatches: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    cfg: PipelineConfig,
    io_specs: Optional[Sequence[StageIOSpec]] = None,
):
    """Run the µbatch stream through the spatial pipeline (one-shot sugar
    over :func:`build_pipeline` — for repeated serving build the runner
    once, or use the ``Engine``).

    Args:
      stage_fn: either a sequence of S per-stage callables
        ``(params_s, x) -> y`` (heterogeneous stages — shapes may differ
        per boundary), or a single callable shared by every stage (the
        homogeneous sugar).
      stage_params: a list of per-stage param pytrees (one per stage; the
        pytrees may differ in structure and leaf shapes). With a single
        shared ``stage_fn``, a pytree whose leaves are stacked on a
        leading axis of size ``n_stages`` is also accepted.
      microbatches: (M, mb, *elem) stacked µbatch inputs. With
        ``cfg.data_axis`` set, the ``mb`` dimension is sharded along that
        mesh axis (each data column pipelines its own batch shard).
      mesh: mesh containing ``cfg.stage_axis`` (and ``cfg.data_axis``).
      io_specs: per-stage :class:`StageIOSpec` (the compiler emits these
        from the topology's geometry); derived via ``jax.eval_shape``
        chaining when omitted.

    Returns:
      (M, mb, *out_elem) outputs of the final stage.
    """
    S = cfg.n_stages
    if callable(stage_fn):
        stage_fns = [stage_fn] * S
        if not isinstance(stage_params, (list, tuple)):
            # Homogeneous sugar: leaves stacked on a leading stage axis.
            stage_params = [
                jax.tree_util.tree_map(lambda l, s=s: l[s], stage_params)
                for s in range(S)
            ]
    else:
        stage_fns = list(stage_fn)
    if io_specs is None:
        io_specs = derive_io_specs(
            stage_fns, stage_params, tuple(microbatches.shape[2:])
        )
    runner = build_pipeline(
        stage_fns,
        stage_params,
        mesh=mesh,
        cfg=cfg,
        io_specs=io_specs,
        microbatch=microbatches.shape[1],
        dtype=microbatches.dtype,
    )
    return runner(microbatches)


def stack_stage_params(per_stage_params: list):
    """Stack a list of per-stage param pytrees along a new leading axis
    (homogeneous-stage sugar for :func:`pipeline_forward`)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params
    )


def make_conv_stage(
    *,
    padding: str = "SAME",
    act: str = "relu",
    pool: int = 0,
    pool_stride: int | None = None,
    stride: int = 1,
    act_bits: int | None = None,
    backend: str | None = None,
    n_out: int = 0,
    kernel: int = 0,
):
    """Build a single-layer pipeline stage body — a compiler-emitted DHM
    actor chain (conv -> bias -> activation (-> pool -> stream quant)) as
    one fused kernel call on ``params = {"w": (K, K, C, N), "b": (N,)}``.

    Thin veneer over :func:`repro.core.dhm.compiler.emit_conv_stage`: the
    layer description goes through the same validated ``ConvLayerSpec``
    dataclass as ``compile_dhm`` topologies, so the pipeline stage bodies
    and the single-device plans share ONE lowering path (act / pool /
    padding / stride are validated at build time there). ``n_out`` and
    ``kernel`` describe the expected parameter geometry; they default to 0
    ("any") because the emitted stage body takes its shapes from the
    params at call time.
    """
    from repro.core.dhm.compiler import emit_conv_stage
    from repro.models.cnn import ConvLayerSpec

    spec = ConvLayerSpec(
        n_out=n_out,
        kernel=kernel,
        padding=padding,
        pool=pool,
        act=act,
        stride=stride,
        pool_stride=pool_stride,
    )
    return emit_conv_stage((spec,), backend=backend, act_bits=act_bits)
