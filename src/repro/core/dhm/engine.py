"""Execution + serving subsystem for compiled DHM plans.

``compiler.py`` is the *lowering* pass (topology -> DPN -> stages -> fused
kernel closures); this module is where compiled plans *execute*:

- :func:`forward` — the eager stage/head composition (``cnn_apply``'s
  path: a fresh per-call plan must not retrace a per-plan jit, so eval
  loops keep the process-wide kernel caches).
- :func:`plan_jitted_forward` — the plan's cached end-to-end jitted
  closure (conv stages + FC head as ONE compiled computation); the
  ``donate=True`` variant transfers input-buffer ownership to XLA for
  serving loops.
- :func:`pipeline_spec` / :func:`run_pipelined` — spatial execution on a
  mesh: per-stage closures + per-edge :class:`StageIOSpec` geometry feed
  the heterogeneous GPipe executor (``pipeline.pipeline_forward``), with
  optional data-parallel batch sharding on a 2D ``(stage, data)`` mesh.
- :class:`Engine` — the fault-tolerant continuous-batching server every
  consumer routes through. Requests carry per-request deadlines
  (``submit(x, deadline_ms=...)``); a background flush loop packs a
  micro-batch when it fills *or* the earliest deadline approaches;
  admission control bounds the queue (``block | reject | shed_oldest``)
  and validates every frame at the gate; dispatch runs under a watchdog
  timeout with bounded retry-with-backoff; persistent failures demote the
  engine down a health-checked execution ladder (mesh pipeline ->
  single-device fused plan -> per-layer plan -> ``ref`` backend) instead
  of taking the process down. Failures surface as structured per-request
  errors (:class:`DeadlineExceeded`, :class:`Rejected`, :class:`Shed`,
  :class:`InvalidRequest`, :class:`BatchFailed`) — ``result()`` raises,
  it never hangs. A seed-driven :class:`~repro.core.dhm.faults.FaultPlan`
  injects failures deterministically for the chaos suite.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dhm import spans
from repro.core.dhm.faults import FaultPlan, InjectedDeviceLoss
from repro.core.dhm.pipeline import CollectiveTimeout, call_with_timeout

_LOG = logging.getLogger("repro.dhm.engine")


# ---------------------------------------------------------------------------
# Plan execution (extracted from compiler.py — the compiler lowers, the
# engine runs).


def forward(plan, x: jax.Array) -> jax.Array:
    """Eager single-device forward: sequential fused stages + FC head.
    x: (B, H, W, C) NHWC -> logits (B, n_classes)."""
    return plan.head_fn(plan.features(x))


def plan_jitted_forward(plan, *, donate: bool = False) -> Callable:
    """The plan's cached end-to-end jitted closure (conv stages + FC head
    as ONE compiled computation — no per-stage Python re-entry, no eager
    head ops). Built once per plan and reused across calls, so repeated
    inference never retraces.

    ``donate=True`` returns a variant that donates the input buffer to the
    computation (XLA may reuse its memory for intermediates) — for serving
    loops that hand off ownership; the caller's array is invalidated, so
    the default keeps the input alive.
    """
    cache = getattr(plan, "_fwd_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_fwd_cache", cache)
    if donate not in cache:
        cache[donate] = jax.jit(
            lambda xb: plan.head_fn(plan.features(xb)),
            donate_argnums=(0,) if donate else (),
        )
    return cache[donate]


def pipeline_spec(plan):
    """The heterogeneous pipeline description of a compiled plan: per-stage
    closures, per-stage params, and the per-edge activation geometry
    (:class:`~repro.core.dhm.pipeline.StageIOSpec` per stage, computed by
    the compiler from the topology)."""
    return (
        [st.fn for st in plan.stages],
        [plan.stage_params(s) for s in range(plan.n_stages)],
        tuple(st.io for st in plan.stages),
    )


def build_plan_pipeline(plan, *, mesh, cfg, microbatch=None):
    """Build the plan's spatial-pipeline runner once (params boxed,
    stacked and made resident per stage device group) — the repeated-
    serving path the ``Engine`` jits with the leaves passed as
    arguments."""
    from repro.core.dhm.pipeline import build_pipeline

    stage_fns, stage_params, io_specs = pipeline_spec(plan)
    return build_pipeline(
        stage_fns, stage_params, mesh=mesh, cfg=cfg, io_specs=io_specs,
        microbatch=microbatch,
    )


def run_pipelined(
    plan, microbatches, *, mesh, cfg=None, data_axis=None,
    overlap=False, edge_mode="auto",
):
    """Stream (M, mb, H, W, C) µbatches through the plan's conv stages on
    a mesh (one device group per stage; heterogeneous stage shapes flow
    over exact-shape-class ICI edges — ``edge_mode="boxed"`` forces the
    max-shape fallback, ``overlap=True`` double-buffers the edge slots).
    Returns the feature stream; apply ``plan.head_fn`` after re-flattening
    for logits."""
    from repro.core.dhm.pipeline import PipelineConfig

    if cfg is None:
        cfg = PipelineConfig(
            plan.n_stages, microbatches.shape[0], data_axis=data_axis,
            overlap=overlap, edge_mode=edge_mode,
        )
    runner = build_plan_pipeline(
        plan, mesh=mesh, cfg=cfg, microbatch=microbatches.shape[1]
    )
    return runner(microbatches)


# ---------------------------------------------------------------------------
# Structured per-request errors: a request always completes — with logits
# or with one of these; ``result()`` raises, it never hangs.


class RequestError(RuntimeError):
    """Base class of structured per-request serving failures."""


class DeadlineExceeded(RequestError):
    """The request's SLO deadline passed before it could be dispatched."""


class Rejected(RequestError):
    """Admission control turned the request away (queue full, policy
    ``reject``)."""


class Shed(Rejected):
    """The request was admitted but later evicted to make room for newer
    work (queue full, policy ``shed_oldest``)."""


class InvalidRequest(RequestError):
    """Gate validation failed the request (non-finite frames / bad dtype)
    — it never entered a packed batch, so it cannot poison one."""


class BatchFailed(RequestError):
    """The request's batch failed on every rung of the execution ladder
    (after retries and demotion) — resubmit or inspect the engine log."""


class LadderExhausted(RuntimeError):
    """Every rung of the execution ladder failed for the current batch;
    the engine stays on its last rung and keeps accepting work."""


class FlusherWedged(RuntimeError):
    """``stop()`` could not join the background flush thread within its
    timeout — a dispatch is stuck past the watchdog. The engine has
    already completed every still-queued request with :class:`Shed`
    (nothing hangs), but the wedged thread may leak; the condition is
    raised loudly instead of being silently swallowed at interpreter
    shutdown."""


class _PoisonedBatch(RuntimeError):
    """Internal: a packed batch carries non-finite input frames — rerun
    the requests isolated instead of retrying or demoting."""


class _NonFiniteOutput(RuntimeError):
    """Internal: a dispatch produced non-finite logits from finite inputs
    (corrupted activations / bad rung) — transient, retry then demote."""


ADMISSION_POLICIES = ("block", "reject", "shed_oldest")


# ---------------------------------------------------------------------------
# Requests + stats.


@dataclasses.dataclass
class Request:
    """One submitted inference request (a batch of frames).

    Completes exactly once: either with logits (``result()`` returns) or
    with a structured :class:`RequestError` (``result()`` raises). With a
    deadline, the flusher guarantees completion by ``deadline_at`` (give
    or take the flush interval) — success or :class:`DeadlineExceeded`.
    """

    index: int
    n_frames: int
    submitted_at: float
    deadline_at: Optional[float]
    _engine: "Engine"
    _frames: Optional[jax.Array] = None
    _result: Optional[jax.Array] = None
    _error: Optional[BaseException] = None
    done_at: Optional[float] = None
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False
    )

    @property
    def done(self) -> bool:
        """The request has completed — with a result or with an error."""
        return self._event.is_set()

    @property
    def ok(self) -> bool:
        return self._result is not None

    @property
    def error(self) -> Optional[BaseException]:
        """The structured failure, or None (pending or succeeded)."""
        return self._error

    @property
    def latency_s(self) -> float:
        if self.done_at is None:
            raise RuntimeError("request not finished; call result() first")
        return self.done_at - self.submitted_at

    def result(self, timeout: Optional[float] = None) -> jax.Array:
        """Logits for this request's frames. Flushes the queue if the
        request has not been scheduled yet (or waits for the background
        flusher, up to ``timeout`` seconds). Raises the request's
        structured :class:`RequestError` if it failed — never hangs."""
        if not self._event.is_set():
            if self._engine._flusher_alive():
                budget = 60.0 if timeout is None else timeout
                if not self._event.wait(budget):
                    raise TimeoutError(
                        f"request {self.index} not completed within "
                        f"{budget:.1f}s — flusher wedged?"
                    )
            else:
                self._engine.flush()
        if self._error is not None:
            raise self._error
        if self._result is None:
            raise RuntimeError(
                f"request {self.index} was not completed by flush() — it "
                "was likely dropped by an earlier flush failure; resubmit"
            )
        return self._result


# Per-rung latency reservoir size: enough samples for a stable p99 at
# serving rates, bounded so a long-lived engine never grows without limit.
_LAT_WINDOW = 2048

# Host phases of a flush, in the order of the Engine's per-phase seconds
# (the EngineStats field each accumulates into).
_PHASES = ("pack_s", "stage_s", "device_wait_s", "check_s", "fetch_s",
           "complete_s")
_PACK, _STAGE, _DEVICE_WAIT, _CHECK, _FETCH, _COMPLETE = range(len(_PHASES))


def _percentile_ms(samples, q: float) -> float:
    """q-th percentile of a latency sample list, in milliseconds
    (nearest-rank; 0.0 on an empty pool)."""
    if not samples:
        return 0.0
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx] * 1e3


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Aggregate serving statistics since engine construction (or the
    last :meth:`Engine.reset_stats`).

    Counts every terminal outcome, not only successes: rejected / shed
    admissions, deadline-exceeded and gate-invalid requests, batch
    failures, plus dispatch retries and ladder demotions.
    ``rung_latency_ms`` records p50/p99 **per execution-ladder rung**
    (over a bounded window of recent completions), so a demotion is
    visible as a latency regime change instead of vanishing into one
    aggregate pool.

    The phase counters split a flush's host time. A flush runs its
    micro-batches two deep: it launches group k+1 (stage and closure
    call, without waiting) before it finishes group k (waits for it,
    fetches its logits and checks them on the host copy).
    ``pack_s`` (building each micro-batch's host array: a copy of the
    requests' frames it spans into one fresh array, zero-padded past
    their end, or nothing where it is a view of one request's frames),
    ``stage_s`` (queuing the asynchronous host-to-device copy into a
    fresh buffer),
    ``device_wait_s`` (the blocking wait in a group's finish, which holds
    whatever of the copy, its layout transpose and the program is still
    to run), ``fetch_s`` (the device-to-host read of the logits) and
    ``check_s`` (``isfinite`` on that host copy) lie inside ``busy_s``;
    what they leave of it is the take, deadline filtering, the closure
    calls and the watchdog thread's start and join. ``complete_s`` (the
    scatter to requests) follows each request's ``done_at`` stamp and so
    lies outside ``busy_s``. ``n_slots`` counts the frames dispatched,
    padding included, beside ``n_frames`` answered. ``n_overlapped``
    counts the micro-batches (of ``n_batches``) launched while an
    earlier one of the same flush was unfinished: ``n_overlapped /
    n_batches`` is (G-1)/G over flushes of G groups. ``n_direct``
    counts the micro-batches (of ``n_batches``) staged straight from one
    request's frames, with no host copy before the stage: those a single
    request covers whole, C-contiguous and of the flush's dtype."""

    n_requests: int
    n_frames: int
    n_batches: int  # jitted-closure invocations (incl. padding batches)
    busy_s: float  # wall time spent inside flush()
    mean_latency_s: float
    max_latency_s: float
    n_ok: int = 0
    n_rejected: int = 0
    n_shed: int = 0
    n_deadline_exceeded: int = 0
    n_invalid: int = 0
    n_failed: int = 0
    n_retries: int = 0
    n_demotions: int = 0
    rung: str = ""
    # rung name -> {"p50_ms", "p99_ms", "n"} over the recent window.
    rung_latency_ms: dict = dataclasses.field(default_factory=dict)
    pack_s: float = 0.0
    stage_s: float = 0.0
    device_wait_s: float = 0.0
    check_s: float = 0.0
    fetch_s: float = 0.0
    complete_s: float = 0.0
    n_slots: int = 0
    n_overlapped: int = 0
    n_direct: int = 0

    @property
    def frames_per_busy_s(self) -> float:
        """Frames answered per second spent inside ``flush`` (not per
        second of wall time)."""
        return self.n_frames / self.busy_s if self.busy_s > 0 else 0.0

    @property
    def n_errors(self) -> int:
        """Requests that completed with a structured error."""
        return (
            self.n_rejected + self.n_shed + self.n_deadline_exceeded
            + self.n_invalid + self.n_failed
        )

    def summary(self) -> str:
        s = (
            f"{self.n_requests} requests / {self.n_frames} frames in "
            f"{self.n_batches} micro-batches ({self.n_direct} staged "
            "direct): "
            f"{self.frames_per_busy_s:.0f} frames/busy-s, latency mean "
            f"{self.mean_latency_s * 1e3:.2f} ms "
            f"max {self.max_latency_s * 1e3:.2f} ms"
        )
        if self.n_errors:
            s += (
                f"; errors: {self.n_rejected} rejected, {self.n_shed} shed, "
                f"{self.n_deadline_exceeded} deadline-exceeded, "
                f"{self.n_invalid} invalid, {self.n_failed} failed"
            )
        if self.n_retries:
            s += f"; {self.n_retries} dispatch retries"
        if self.n_demotions:
            s += f"; {self.n_demotions} demotions"
        if self.rung:
            s += f" (rung: {self.rung})"
        for rung, lat in self.rung_latency_ms.items():
            s += (
                f"\n  rung {rung}: p50 {lat['p50_ms']:.2f} ms "
                f"p99 {lat['p99_ms']:.2f} ms ({lat['n']} samples)"
            )
        return s


@dataclasses.dataclass
class _Flight:
    """One launched micro-batch: its logits on the device until the
    finish fetches them (then None), its host frames (the finish's
    poisoned-input check reads them), the injected stall the finish
    sleeps, its ``group`` span row, when the closure was called (the
    ``forward`` span's start), its stage seconds, whether an earlier
    group of its flush was unfinished at its launch, and whether its
    frames are a view of one request's frames."""

    out: Optional[jax.Array]
    frames: np.ndarray
    stall_s: float
    row: int
    t_launch: float
    stage_s: float
    overlapped: bool
    direct: bool


# ---------------------------------------------------------------------------
# The serving engine.


class Engine:
    """Fault-tolerant continuous-batching server around a
    :class:`CompiledDHM` plan.

    Requests (frames or frame batches) enter a bounded queue via
    :meth:`submit`, each optionally carrying a latency SLO
    (``deadline_ms``). :meth:`flush` packs the queue into fixed-size
    micro-batches (tail padded with zero frames, outputs sliced back per
    request) and runs them through the active rung's **donated** jitted
    closure; with :meth:`start` (or ``auto_flush=True``, or the context
    manager) a background flush loop does this continuously — a batch is
    dispatched when it fills *or* when the earliest queued deadline
    approaches, and requests whose deadline passed complete with
    :class:`DeadlineExceeded` instead of blocking the batch.

    **Admission control** (``max_queue`` + ``admission``): a full queue
    blocks the submitter, rejects the new request, or sheds the oldest
    queued one — always with a structured error, never silent loss. Gate
    validation (``validate=True``) fails non-finite / wrong-dtype frames
    at submit, so one bad frame can never poison a packed batch; if a bad
    frame does slip in (``validate=False``), the poisoned batch is rerun
    with each request isolated and only the invalid ones fail.

    **Graceful degradation**: execution runs on a health-checked ladder —
    mesh pipeline (when ``mesh`` is given) -> single-device fused plan ->
    per-layer plan (the ``vmem_budget=0`` lowering) -> ``ref`` backend.
    Each dispatch runs under a watchdog timeout
    (:func:`~repro.core.dhm.pipeline.call_with_timeout`); transient
    failures retry with exponential backoff, and a rung that keeps
    raising, times out, or loses a device is demoted with a logged reason
    (``engine.demotions``). A rung is only promoted into service after
    the plan passes its compiler self-check and the rung's closure
    completes a warmup probe.

    ``fault_plan`` injects deterministic failures
    (:mod:`repro.core.dhm.faults`) for chaos testing.
    """

    def __init__(
        self,
        plan,
        *,
        name: Optional[str] = None,
        microbatch: int = 8,
        mesh=None,
        n_microbatches=4,  # int, or "auto" to run the µbatch autotuner
        data_axis: Optional[str] = None,
        stage_axis: str = "stage",
        overlap: bool = False,
        edge_mode: str = "auto",
        tuning=None,  # a throughput.PipelineTuning overriding the knobs
        donate: bool = True,
        warmup: bool = True,
        # -- robustness knobs -------------------------------------------
        max_queue: int = 0,
        admission: str = "block",
        default_deadline_ms: Optional[float] = None,
        deadline_margin_ms: float = 2.0,
        validate: bool = True,
        check_outputs: bool = True,
        auto_flush: bool = False,
        flush_interval_ms: float = 5.0,
        dispatch_timeout_s: Optional[float] = 120.0,
        warmup_timeout_s: Optional[float] = None,
        max_retries: int = 2,
        retry_backoff_s: float = 0.005,
        allow_degraded: bool = True,
        fault_plan: Optional[FaultPlan] = None,
    ):
        # Autotuned pipeline geometry: an explicit PipelineTuning (from
        # throughput.autotune_pipeline over measured sweeps) or
        # n_microbatches="auto" (model-priced grid — no measurements)
        # overrides microbatch/n_microbatches/overlap/edge_mode.
        if tuning is None and n_microbatches == "auto":
            if mesh is None:
                raise ValueError(
                    'n_microbatches="auto" needs a mesh to tune for'
                )
            from repro.core.dhm.throughput import autotune_pipeline

            tuning = autotune_pipeline(plan, mesh.size)
        if tuning is not None:
            microbatch = tuning.microbatch
            n_microbatches = tuning.n_microbatches
            overlap = tuning.overlap
            edge_mode = tuning.edge_mode
        self.tuning = tuning
        if microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {microbatch}")
        admission = admission.replace("-", "_")
        if admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission policy {admission!r}; expected one of "
                f"{ADMISSION_POLICIES}"
            )
        if max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        if mesh is not None and (
            not isinstance(n_microbatches, int) or n_microbatches < 1
        ):
            raise ValueError(
                f"n_microbatches must be >= 1, got {n_microbatches}"
            )
        self.plan = plan
        self.microbatch = microbatch
        self.mesh = mesh
        self.n_microbatches = n_microbatches
        self.data_axis = data_axis
        self.stage_axis = stage_axis
        self.overlap = overlap
        self.edge_mode = edge_mode
        self.donate = donate
        self.warmup = warmup
        self.max_queue = max_queue
        self.admission = admission
        self.default_deadline_ms = default_deadline_ms
        self.deadline_margin_ms = deadline_margin_ms
        self.validate = validate
        self.check_outputs = check_outputs
        self.flush_interval_ms = flush_interval_ms
        self.dispatch_timeout_s = dispatch_timeout_s
        # Warmup probes include compile time, which is unbounded by design;
        # ``dispatch_timeout_s`` watches steady-state dispatches only (the
        # probe has already compiled the rung's closure at the serving
        # shape). Set this to also bound rung warmup/compilation.
        self.warmup_timeout_s = warmup_timeout_s
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self._faults = fault_plan
        # Tenant name: threaded into fault hooks so a FaultPlan can scope
        # its trigger windows to ONE tenant's engine (bulkhead chaos
        # testing); None = the untenanted single-engine stream.
        self.name = name

        h, w = plan.topo.input_shape
        self._frame_shape = (h, w, plan.topo.input_channels)
        # Frames one jitted-closure invocation consumes.
        self.group = (
            microbatch if mesh is None else microbatch * n_microbatches
        )

        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._flush_lock = threading.Lock()
        self._queue: list = []  # pending Requests (frames attached)
        self._queue_frames = 0
        self._requests = 0
        # Stats report requests relative to this base so ``reset_stats``
        # can zero the window without reusing request indices.
        self._requests_base = 0
        self._frames = 0
        self._batches = 0
        self._slots = 0
        self._overlapped = 0
        self._direct = 0
        self._busy_s = 0.0
        self._phase_s = [0.0] * len(_PHASES)
        self._n_flushes = 0
        # The armed span log (start_spans / stop_spans), None while off.
        self._spans: Optional[spans.SpanLog] = None
        # Running latency aggregates (a serving engine lives long — no
        # per-request history kept).
        self._lat_n = 0
        self._lat_sum = 0.0
        self._lat_max = 0.0
        # Per-rung latency reservoirs (rung -> deque of recent latencies):
        # a demotion shows up as a new rung key with its own p50/p99
        # instead of smearing into the aggregate pool.
        self._rung_lat: dict = {}
        # Terminal-outcome counters beyond success.
        self._n_ok = 0
        self._n_rejected = 0
        self._n_shed = 0
        self._n_deadline = 0
        self._n_invalid = 0
        self._n_failed = 0
        self._n_retries = 0
        self.demotions: list = []  # [{"rung", "reason"}] per rung left
        self._flusher: Optional[threading.Thread] = None
        # A router's scheduler registers itself here (a zero-arg liveness
        # predicate): while it is alive the engine behaves as if a
        # background flusher runs — ``result()`` waits and block-policy
        # submits park on the condition instead of inline-draining.
        self._external_flusher: Optional[Callable[[], bool]] = None
        self._stop = threading.Event()

        # The execution ladder, best rung first. Each entry is
        # (name, closure factory); a rung is activated lazily and only
        # after the plan self-check + a warmup probe pass.
        self._ladder: list = []
        if mesh is not None:
            self._ladder.append(("mesh", self._build_mesh_fwd))
        self._ladder.append(("fused", self._build_fused_fwd))
        if allow_degraded:
            self._ladder.append(
                ("per_layer", lambda: self._build_unfused_fwd(plan.backend))
            )
            if getattr(plan, "backend", "ref") != "ref":
                self._ladder.append(
                    ("ref", lambda: self._build_unfused_fwd("ref"))
                )
        # Health probe: a plan that fails its own self-check (non-finite
        # baked params, inconsistent stage IO) must not serve at all.
        if hasattr(plan, "self_check"):
            plan.self_check()
        self._rung_idx = -1
        self._rung_name = ""
        self._fwd: Optional[Callable] = None
        if not self._activate_rung(0, reason=None):
            raise LadderExhausted(
                "no rung of the execution ladder passed its warmup probe"
            )
        if auto_flush:
            self.start()

    # -- execution ladder ---------------------------------------------------

    @property
    def rung(self) -> str:
        """Name of the ladder rung currently serving."""
        return self._rung_name

    def _build_fused_fwd(self) -> Callable:
        return plan_jitted_forward(self.plan, donate=self.donate)

    def _build_unfused_fwd(self, backend: str) -> Callable:
        """A degraded single-device closure: per-layer kernel calls (the
        ``vmem_budget=0`` lowering) on ``backend``, same baked params and
        head as the plan."""
        from repro.core.dhm.compiler import emit_conv_stage

        plan = self.plan
        stage_fns = [
            emit_conv_stage(
                st.specs, backend=backend, first_layer=st.conv_layers[0],
                **plan.stage_quant_kwargs(st.index),
            )
            for st in plan.stages
        ]

        def _fwd(xb):
            for s, fn in enumerate(stage_fns):
                xb = fn(plan.stage_params(s), xb)
            return plan.head_fn(xb)

        return jax.jit(_fwd, donate_argnums=(0,) if self.donate else ())

    def _build_mesh_fwd(self) -> Callable:
        from repro.core.dhm.pipeline import PipelineConfig

        plan, mesh = self.plan, self.mesh
        microbatch, n_microbatches = self.microbatch, self.n_microbatches
        cfg = PipelineConfig(
            plan.n_stages, n_microbatches, stage_axis=self.stage_axis,
            data_axis=self.data_axis, overlap=self.overlap,
            edge_mode=self.edge_mode,
        )
        # Box + stack + make the per-stage params resident ONCE, here
        # (eagerly — stacked inside the jit trace they would be re-stacked
        # on every dispatch); the jitted closure then takes the resident
        # leaves as arguments.
        runner = build_plan_pipeline(
            plan, mesh=mesh, cfg=cfg, microbatch=microbatch
        )
        self._runner = runner

        def _pipe_fwd(leaves, frames):
            mbs = frames.reshape(
                (n_microbatches, microbatch) + frames.shape[1:]
            )
            feats = runner.apply(leaves, mbs)
            flat = feats.reshape(
                (n_microbatches * microbatch,) + feats.shape[2:]
            )
            return plan.head_fn(flat)

        pipe_jit = jax.jit(
            _pipe_fwd, donate_argnums=(1,) if self.donate else ()
        )
        return lambda frames: pipe_jit(runner.stacked_leaves, frames)

    @staticmethod
    def _demotion_record(rung: str, cause) -> dict:
        """A demotion ledger entry; when the cause is a
        :class:`PlanCheckError` (or anything else carrying registry
        ``invariants``), the record cites the failed invariant IDs so the
        ledger names the same checks CI's static gate enforces."""
        rec = {"rung": rung, "reason": str(cause)}
        ids = getattr(cause, "invariants", ())
        if ids:
            rec["invariants"] = list(ids)
        return rec

    def _activate_rung(
        self, idx: int, reason: Optional[str], cause=None
    ) -> bool:
        """Walk the ladder from ``idx`` until a rung builds and passes its
        warmup probe; record every rung skipped or left as a demotion.
        Returns False when the ladder is exhausted (current rung kept)."""
        if reason is not None and self._rung_name:
            self.demotions.append(
                self._demotion_record(self._rung_name, cause or reason)
            )
            _LOG.warning(
                "engine demoting off rung %r: %s", self._rung_name, reason
            )
        while idx < len(self._ladder):
            name, factory = self._ladder[idx]
            try:
                fwd = factory()
                if self.warmup:
                    probe = jnp.zeros(
                        (self.group,) + self._frame_shape, jnp.float32
                    )

                    def _probe():
                        out = fwd(self._stage(probe))
                        return jax.block_until_ready(out)

                    out = call_with_timeout(
                        _probe,
                        timeout_s=self.warmup_timeout_s,
                        what=f"warmup probe (rung {name})",
                    )
                    if not bool(jnp.isfinite(out).all()):
                        raise _NonFiniteOutput(
                            f"rung {name} warmup probe produced non-finite "
                            "logits"
                        )
            except Exception as e:  # noqa: BLE001 — any failure demotes
                self.demotions.append(self._demotion_record(name, e))
                _LOG.warning(
                    "engine rung %r failed its warmup probe: %s", name, e
                )
                idx += 1
                continue
            self._rung_idx = idx
            self._rung_name = name
            self._fwd = fwd
            return True
        return False

    def _demote(self, cause: BaseException) -> None:
        if not self._activate_rung(
            self._rung_idx + 1, reason=str(cause), cause=cause
        ):
            raise LadderExhausted(
                f"every execution-ladder rung failed (last: {cause})"
            ) from cause

    # -- request queue + admission -------------------------------------------

    def submit(
        self, x: jax.Array, *, deadline_ms: Optional[float] = None
    ) -> Request:
        """Enqueue a frame ((H, W, C)) or batch of frames ((B, H, W, C));
        returns a :class:`Request` whose ``result()`` yields its logits or
        raises its structured error.

        ``deadline_ms`` is the request's latency SLO: the background
        flusher dispatches early to honor it, and once it expires the
        request completes with :class:`DeadlineExceeded` instead of
        holding up the batch. Malformed shapes raise ``ValueError``
        immediately (a caller bug); non-finite or wrong-dtype frames fail
        the request with :class:`InvalidRequest` at the gate (bad data
        must never enter a packed batch). A full queue is handled per the
        engine's admission policy.
        """
        req = self._new_request(x, deadline_ms=deadline_ms)
        if req.done:  # failed at the validation gate
            return req
        return self._enqueue(req)

    def _new_request(
        self, x: jax.Array, *, deadline_ms: Optional[float] = None
    ) -> Request:
        """Parse + gate-validate frames into a :class:`Request` WITHOUT
        enqueueing it (the router uses this to fail a request fast —
        e.g. circuit open — before it ever touches the queue). Malformed
        shapes raise ``ValueError``; gate failures return the request
        already completed with :class:`InvalidRequest`."""
        # Queued frames live on the HOST: the flush builds each group's
        # array with numpy (eager device concats would compile per
        # distinct shape), or takes it as it is where one request's
        # frames fill the group, and only that fixed-shape array is staged
        # onto the device.
        x = np.asarray(x)
        if x.shape == self._frame_shape:
            x = x[None]
        if x.ndim != 4 or tuple(x.shape[1:]) != self._frame_shape:
            raise ValueError(
                f"expected frames of shape {self._frame_shape} (optionally "
                f"batched), got {tuple(x.shape)}"
            )
        now = time.perf_counter()
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        with self._lock:
            index = self._requests
            self._requests += 1
        req = Request(
            index=index,
            n_frames=x.shape[0],
            submitted_at=now,
            deadline_at=(
                now + deadline_ms / 1e3 if deadline_ms is not None else None
            ),
            _engine=self,
            _frames=x,
        )
        if self.validate:
            if not jnp.issubdtype(x.dtype, jnp.floating):
                self._fail(
                    req,
                    InvalidRequest(
                        f"request {req.index}: frames must be floating "
                        f"point, got dtype {x.dtype}"
                    ),
                )
                return req
            if not bool(np.isfinite(x).all()):
                self._fail(
                    req,
                    InvalidRequest(
                        f"request {req.index}: frames contain NaN/Inf — "
                        "rejected at the admission gate"
                    ),
                )
                return req
        return req

    def _enqueue(self, req: Request) -> Request:
        """Admit a gate-validated request into the bounded queue per the
        engine's admission policy (block | reject | shed_oldest)."""
        while True:
            with self._cv:
                if not self.max_queue or len(self._queue) < self.max_queue:
                    self._queue.append(req)
                    self._queue_frames += req.n_frames
                    self._cv.notify_all()
                    return req
                if self.admission == "reject":
                    self._fail(
                        req,
                        Rejected(
                            f"request {req.index}: queue full "
                            f"({self.max_queue} requests), policy=reject"
                        ),
                    )
                    return req
                if self.admission == "shed_oldest":
                    victim = self._queue.pop(0)
                    self._queue_frames -= victim.n_frames
                    self._fail(
                        victim,
                        Shed(
                            f"request {victim.index}: shed by newer work "
                            f"(queue full at {self.max_queue} requests, "
                            "policy=shed_oldest)"
                        ),
                    )
                    continue
                # policy == "block": wait for the flusher to drain...
                if self._flusher_alive():
                    self._cv.wait(timeout=0.05)
                    continue
            # ...or drain inline when no background flusher runs.
            self.flush()

    def _fail(self, req: Request, err: RequestError) -> None:
        """Complete a request with a structured error (exactly once)."""
        with self._lock:
            if req.done:
                return
            if isinstance(err, Shed):
                self._n_shed += 1
            elif isinstance(err, Rejected):
                self._n_rejected += 1
            elif isinstance(err, DeadlineExceeded):
                self._n_deadline += 1
            elif isinstance(err, InvalidRequest):
                self._n_invalid += 1
            else:
                self._n_failed += 1
            req._error = err
            req.done_at = time.perf_counter()
            req._frames = None
            req._event.set()

    def _complete(self, req: Request, logits: jax.Array, done: float) -> None:
        with self._lock:
            if req.done:
                return
            req._result = logits
            req.done_at = done
            req._frames = None
            req._event.set()
            lat = done - req.submitted_at
            self._lat_n += 1
            self._lat_sum += lat
            self._lat_max = max(self._lat_max, lat)
            self._rung_lat.setdefault(
                self._rung_name, collections.deque(maxlen=_LAT_WINDOW)
            ).append(lat)
            self._n_ok += 1
            self._frames += req.n_frames

    # -- dispatch: faults, watchdog, retry, demotion --------------------------

    def _stage(self, batch: jax.Array) -> jax.Array:
        """Stage a micro-batch's host array into a fresh buffer the closure
        can consume. The copy is what makes donation safe (the caller's
        arrays, which a micro-batch staged direct is a view of, stay valid,
        and a failed dispatch can restage for its retry). The copy is
        queued asynchronously, so within a flush batch k+1 is staged and
        launched while batch k is still being computed on — the
        double-buffered serving path (:meth:`_run_groups`)."""
        return jnp.array(batch, copy=True)

    def _corrupted_forward(self, frames: jax.Array, stage: int) -> jax.Array:
        """Eager forward with NaN corruption injected at the boundary
        after conv stage ``stage`` (the fault-injection path — models
        silent mid-pipeline data corruption)."""
        x = self._stage(frames)
        for st in self.plan.stages:
            x = st.fn(self.plan.stage_params(st.index), x)
            if st.index == stage:
                x = jnp.full_like(x, jnp.nan)
        return self.plan.head_fn(x)

    def _split(self, parts: list) -> list:
        """The frames of ``parts`` (the requests' host arrays, in order)
        cut into micro-batches of ``group``: for each, the views of the
        requests' frames it holds. The last may hold fewer frames."""
        g = self.group
        groups, cur, room = [], [], g
        for p in parts:
            s = 0
            while s < len(p):
                take = min(room, len(p) - s)
                cur.append(p[s : s + take])
                s += take
                room -= take
                if not room:
                    groups.append(cur)
                    cur, room = [], g
        if cur:
            groups.append(cur)
        return groups

    def _pack(self, parts: list, dtype, row: int, phases: list) -> tuple:
        """The host array of the micro-batch whose frames are ``parts``
        (views of requests' frames, in order), and whether it is staged
        direct. One part that fills the group, C-contiguous and of
        ``dtype``, is used as it is; otherwise the parts are copied into
        one fresh array of ``dtype``, zero-padded past their end. Adds its
        seconds to ``phases`` and records a ``pack`` span under the
        group's span ``row``."""
        t_p = time.perf_counter()
        g = self.group
        first = parts[0]
        direct = (
            len(parts) == 1 and len(first) == g and first.dtype == dtype
            and first.flags.c_contiguous
        )
        if direct:
            x = first
        else:
            x = np.empty((g,) + self._frame_shape, dtype)
            off = 0
            for p in parts:
                x[off : off + len(p)] = p
                off += len(p)
            x[off:] = 0
        t_q = time.perf_counter()
        phases[_PACK] += t_q - t_p
        log = self._spans
        if log is not None:
            log.add(spans.PACK, 0, row, t_p, t_q)
        return x, direct

    def _launch(
        self, frames: np.ndarray, row: int = -1, overlapped: bool = False,
        direct: bool = False,
    ) -> _Flight:
        """Launch one exactly-``group``-sized batch on the active rung
        without waiting for it: draw its fault effects (an injected
        exception is raised here), stage it, and call the rung's closure
        (or the corrupted forward under an injected NaN). Runs on the
        calling thread; :meth:`_finish` waits for the result. ``row`` is
        the group's span row; ``overlapped`` says an earlier group of the
        flush is still unfinished; ``direct`` that ``frames`` is a view of
        one request's frames."""
        stall_s, corrupt = 0.0, None
        if self._faults is not None:
            eff = self._faults.dispatch_effects(
                rung=self._rung_name, tenant=self.name
            )
            if eff.exc is not None:
                raise eff.exc
            stall_s, corrupt = eff.stall_s, eff.corrupt_stage
        t_a = time.perf_counter()
        if corrupt is not None:
            t_b = t_a
            out = self._corrupted_forward(frames, corrupt)
        else:
            staged = self._stage(frames)
            t_b = time.perf_counter()
            out = self._fwd(staged)
        log = self._spans
        if log is not None:
            log.add(spans.STAGE, 0, row, t_a, t_b)
        return _Flight(
            out, frames, stall_s, row, t_b, t_b - t_a, overlapped, direct
        )

    def _finish(self, fl: _Flight, phases: list) -> np.ndarray:
        """Wait for a launched group under the watchdog
        (``dispatch_timeout_s``; an injected stall is slept first), fetch
        its logits to the host and check them there. Returns the host
        logits; the device array is dropped before this returns. Raises
        :class:`CollectiveTimeout`, :class:`_PoisonedBatch` (the inputs
        themselves are non-finite) or :class:`_NonFiniteOutput`."""

        def _wait():
            try:
                if fl.stall_s:
                    time.sleep(fl.stall_s)
                t_c = time.perf_counter()
                jax.block_until_ready(fl.out)
                t_d = time.perf_counter()
                host = np.asarray(fl.out)
            finally:
                # Drop the device logits here, before the scatter:
                # releasing a device array can hand the interpreter to the
                # callers the scatter wakes, and the flush loop would then
                # take part of their resubmissions (a flush of padded
                # groups).
                fl.out = None
            t_e = time.perf_counter()
            ok = not self.check_outputs or bool(np.isfinite(host).all())
            return host, ok, (t_c, t_d, t_e, time.perf_counter())

        host, ok, (t_c, t_d, t_e, t_f) = call_with_timeout(
            _wait,
            timeout_s=self.dispatch_timeout_s,
            what=f"dispatch (rung {self._rung_name})",
        )
        with self._lock:
            self._batches += 1
            self._slots += self.group
            self._overlapped += fl.overlapped
            self._direct += fl.direct
        phases[_STAGE] += fl.stage_s
        phases[_DEVICE_WAIT] += t_d - t_c
        phases[_FETCH] += t_e - t_d
        phases[_CHECK] += t_f - t_e
        log = self._spans
        if log is not None:
            log.add(spans.FORWARD, 0, fl.row, fl.t_launch, t_d)
            log.add(spans.FETCH, 0, fl.row, t_d, t_e)
            log.add(spans.CHECK, 0, fl.row, t_e, t_f)
        if not ok:
            if not bool(np.isfinite(fl.frames).all()):
                raise _PoisonedBatch(
                    "packed batch carries non-finite input frames"
                )
            raise _NonFiniteOutput(
                f"rung {self._rung_name} produced non-finite logits from "
                "finite inputs"
            )
        return host

    def _run_group(
        self,
        parts: list,
        phases: Optional[list] = None,
        parent: int = -1,
        gid: int = 0,
        failed: Optional[Exception] = None,
        dtype=None,
    ) -> np.ndarray:
        """Run one micro-batch, whose frames are ``parts`` (at most
        ``group`` of them, in order), through the active rung, one launch
        and finish at a time: fault effects applied, watchdog timeout,
        bounded retry-with-backoff on transient failures, demotion on
        persistent ones. Its host array is built once (:meth:`_pack`, as
        ``dtype``, by default the parts' own result type) and kept until
        it finishes. Returns the logits on the host. Raises
        :class:`LadderExhausted` when no rung can complete the batch, or
        :class:`_PoisonedBatch` when the inputs themselves are non-finite
        (the flush isolates per request). ``failed`` is the error of an
        attempt already made on this batch by the pipelined flush; it
        counts as the first attempt.

        Adds the pack, stage, device-wait, fetch and check seconds to
        ``phases`` (the flush's per-phase list); with spans armed,
        records a ``group`` span (``gid``, holding the parts' frames)
        under the span row ``parent``."""
        if phases is None:
            phases = [0.0] * len(_PHASES)
        if dtype is None:
            dtype = np.result_type(*(p.dtype for p in parts))
        log = self._spans
        grow = (
            -1 if log is None
            else log.begin(spans.GROUP, gid, parent, time.perf_counter())
        )
        backoff = self.retry_backoff_s
        retries_left = self.max_retries
        try:
            frames, direct = self._pack(parts, dtype, grow, phases)
            while True:
                try:
                    if failed is not None:
                        err, failed = failed, None
                        raise err
                    return self._finish(
                        self._launch(frames, grow, direct=direct), phases
                    )
                except _PoisonedBatch:
                    raise
                except (InjectedDeviceLoss, CollectiveTimeout) as e:
                    # Not transient: a lost device or wedged collective will
                    # not heal on retry — demote off the rung immediately.
                    self._demote(e)
                    retries_left = self.max_retries
                    backoff = self.retry_backoff_s
                except Exception as e:  # noqa: BLE001 — retry then demote
                    if retries_left > 0:
                        retries_left -= 1
                        with self._lock:
                            self._n_retries += 1
                        _LOG.info(
                            "dispatch failed on rung %r (%s); retrying in "
                            "%.3fs (%d retries left)",
                            self._rung_name, e, backoff, retries_left,
                        )
                        t_r = time.perf_counter()
                        time.sleep(backoff)
                        if log is not None:
                            log.add(spans.RETRY, 0, grow, t_r,
                                    time.perf_counter())
                        backoff *= 2
                    else:
                        self._demote(e)
                        retries_left = self.max_retries
                        backoff = self.retry_backoff_s
        finally:
            if log is not None:
                log.end(grow, time.perf_counter(), sum(map(len, parts)))

    def _run_groups(self, parts: list, phases: list, parent: int) -> list:
        """Run a flush's frames, the requests' host arrays ``parts`` in
        order, group by group, two deep: launch group k+1, then finish
        group k, so the device computes k+1 while the host waits for,
        fetches and checks k. Each group's host array is built in its
        launch (:meth:`_pack`), as the result type of every part's dtype,
        so a packed group's copy runs while the device computes the group
        before it. At most two groups are in flight. After a failure in
        the launch or the finish of group k, a launched k+1 is dropped,
        and group k and the rest of the flush run through
        :meth:`_run_group`, the failed attempt counting as group k's
        first. Returns the host logits of each group, in order."""
        dtype = np.result_type(*(p.dtype for p in parts))
        groups = self._split(parts)
        count = len(groups)
        log = self._spans

        def end(fl, arg):
            if log is not None:
                log.end(fl.row, time.perf_counter(), arg)

        def launch(k):
            row = (
                -1 if log is None
                else log.begin(spans.GROUP, k, parent, time.perf_counter())
            )
            try:
                frames, direct = self._pack(groups[k], dtype, row, phases)
                return self._launch(frames, row, k > 0, direct)
            except Exception:
                if log is not None:
                    log.end(row, time.perf_counter(), 0)
                raise

        outs = []
        k, failed, ahead = 0, None, None
        try:
            ahead = launch(0)
        except Exception as e:  # noqa: BLE001 — rerun through _run_group
            failed = e
        while failed is None and k < count:
            nxt = None
            if k + 1 < count:
                try:
                    nxt = launch(k + 1)
                except Exception as e:  # noqa: BLE001 — after finishing k
                    failed = e
            try:
                outs.append(self._finish(ahead, phases))
            except Exception as e:  # noqa: BLE001 — rerun through _run_group
                end(ahead, 0)
                if nxt is not None:
                    nxt.out = None  # dropped unfinished
                    end(nxt, 0)
                if isinstance(e, _PoisonedBatch):
                    raise
                failed = e
                break
            end(ahead, sum(map(len, groups[k])))
            ahead, k = nxt, k + 1
        ahead = nxt = None  # let go of their arrays: the reruns rebuild theirs
        for j in range(k, count):
            outs.append(self._run_group(
                groups[j], phases, parent, j, failed if j == k else None,
                dtype,
            ))
        return outs

    # -- flushing -------------------------------------------------------------

    def flush(self, max_frames: Optional[int] = None) -> int:
        """Drain the queue: pack pending frames into ``group``-sized
        micro-batches (zero-padded tail), run each through the active
        rung, and scatter the logits back to their requests. Expired
        deadlines complete with :class:`DeadlineExceeded` at pack time; a
        failed batch is isolated per request so invalid requests fail
        alone. Explicitly a no-op on an empty queue (double-flush safe);
        thread-safe against the background flusher.

        ``max_frames`` bounds one call to roughly that many frames from
        the queue head (always at least one request) — the router's
        deficit-round-robin scheduler uses this to dispatch exactly one
        scheduling quantum per turn. Returns the number of frames taken
        off the queue (0 = nothing pending)."""
        with self._flush_lock:
            return self._flush_once(max_frames)

    def _flush_once(self, max_frames: Optional[int] = None) -> int:
        if self._faults is not None:
            delay = self._faults.on_flush(tenant=self.name)
            if delay:
                time.sleep(delay)
        with self._cv:
            if not self._queue:
                return 0
            if max_frames is None:
                pending, self._queue = self._queue, []
                self._queue_frames = 0
            else:
                # Take whole requests from the head up to ~max_frames
                # (never split a request; always take at least one).
                pending = []
                taken = 0
                while self._queue and (
                    not pending
                    or taken + self._queue[0].n_frames <= max_frames
                ):
                    r = self._queue.pop(0)
                    pending.append(r)
                    taken += r.n_frames
                self._queue_frames -= taken
            self._n_flushes += 1
            flush_no = self._n_flushes
            self._cv.notify_all()
        n_taken = sum(r.n_frames for r in pending)
        t0 = time.perf_counter()
        log = self._spans
        frow = -1
        if log is not None:
            frow = log.begin(spans.FLUSH, flush_no, -1, t0)
            for req in pending:
                log.add(spans.QUEUED, req.index, frow, req.submitted_at, t0)
        live = []
        for req in pending:
            if req.deadline_at is not None and t0 > req.deadline_at:
                self._fail(
                    req,
                    DeadlineExceeded(
                        f"request {req.index}: deadline passed "
                        f"{(t0 - req.deadline_at) * 1e3:.1f} ms before "
                        "dispatch"
                    ),
                )
            else:
                live.append(req)
        if not live:
            if log is not None:
                log.end(frow, time.perf_counter())
            return n_taken
        ph = [0.0] * len(_PHASES)
        done = None
        try:
            # The frames stay on the HOST, in the requests' own arrays:
            # each group's array is built there in its launch, at the one
            # fixed group shape the jitted closure was compiled for (eager
            # jnp concats would compile once per distinct request count).
            n = sum(r.n_frames for r in live)
            outs = self._run_groups(
                [np.asarray(r._frames) for r in live], ph, frow
            )
            logits = (
                outs[0][:n] if len(outs) == 1
                else np.concatenate(outs, axis=0)[:n]
            )
        except _PoisonedBatch:
            self._isolate(live, ph, frow)
        except LadderExhausted as e:
            for req in live:
                self._fail(
                    req,
                    BatchFailed(f"request {req.index}: batch failed — {e}"),
                )
        except Exception as e:  # noqa: BLE001 — never drop requests silently
            _LOG.exception("unexpected flush failure")
            for req in live:
                self._fail(
                    req,
                    BatchFailed(
                        f"request {req.index}: unexpected flush failure — "
                        f"{type(e).__name__}: {e}"
                    ),
                )
        else:
            done = time.perf_counter()
            off = 0
            for req in live:
                self._complete(req, logits[off : off + req.n_frames], done)
                off += req.n_frames
        t_end = time.perf_counter()
        if done is not None:
            ph[_COMPLETE] = t_end - done
        if log is not None:
            if done is not None:
                log.add(spans.COMPLETE, 0, frow, done, t_end)
            log.end(frow, t_end, len(live))
        with self._lock:
            # A failed flush counts until its requests have failed.
            self._busy_s += (t_end if done is None else done) - t0
            for i, s in enumerate(ph):
                self._phase_s[i] += s
        return n_taken

    def _isolate(self, reqs: list, phases: list, parent: int = -1) -> None:
        """Rerun a poisoned batch one request at a time: invalid requests
        fail alone with :class:`InvalidRequest`, the rest recompute
        cleanly — one bad frame never takes down its batchmates."""
        for req in reqs:
            x = np.asarray(req._frames)
            if not bool(np.isfinite(x).all()):
                self._fail(
                    req,
                    InvalidRequest(
                        f"request {req.index}: frames contain NaN/Inf — "
                        "isolated from its batch"
                    ),
                )
                continue
            try:
                outs = [
                    self._run_group(parts, phases, parent, j)
                    for j, parts in enumerate(self._split([x]))
                ]
                logits = np.concatenate(outs, axis=0)[: req.n_frames]
            except (LadderExhausted, _PoisonedBatch) as e:
                self._fail(
                    req,
                    BatchFailed(
                        f"request {req.index}: isolated rerun failed — {e}"
                    ),
                )
                continue
            self._complete(req, logits, time.perf_counter())

    # -- background flush loop ------------------------------------------------

    def _flusher_alive(self) -> bool:
        if self._flusher is not None and self._flusher.is_alive():
            return True
        ext = self._external_flusher
        return bool(ext is not None and ext())

    def start(self) -> "Engine":
        """Start the background flush loop (idempotent): micro-batches are
        dispatched when they fill, when the earliest queued deadline is
        within ``deadline_margin_ms``, or every ``flush_interval_ms`` —
        continuous batching, no cooperative ``flush()`` needed."""
        if self._flusher_alive():
            return self
        self._stop = threading.Event()
        self._flusher = threading.Thread(
            target=self._flush_loop, daemon=True, name="dhm-engine-flusher"
        )
        self._flusher.start()
        return self

    def _shed_all(self, why: str) -> int:
        """Complete every still-queued request with a structured
        :class:`Shed` error (exactly-once semantics hold: a request a
        late-waking flusher already picked up is a no-op here and vice
        versa). Returns the number of requests shed."""
        with self._cv:
            pending, self._queue = self._queue, []
            self._queue_frames = 0
            self._cv.notify_all()
        for req in pending:
            self._fail(req, Shed(f"request {req.index}: {why}"))
        return len(pending)

    def stop(self, *, drain: bool = True, join_timeout_s: float = 30.0) -> None:
        """Stop the background flush loop; by default drain what is still
        queued (every in-flight request still completes).

        The join is bounded: if the flusher does not exit within
        ``join_timeout_s`` (a dispatch wedged past the watchdog), every
        still-queued request is completed with :class:`Shed` — nothing
        hangs — and :class:`FlusherWedged` is raised loudly instead of
        leaking the thread silently into interpreter shutdown."""
        flusher = self._flusher
        if flusher is not None:
            self._stop.set()
            with self._cv:
                self._cv.notify_all()
            flusher.join(timeout=join_timeout_s)
            self._flusher = None
            if flusher.is_alive():
                shed = self._shed_all(
                    "engine stopping with a wedged flush thread"
                )
                raise FlusherWedged(
                    f"flush thread did not exit within {join_timeout_s:.1f}s "
                    f"of stop(); {shed} queued request(s) completed with "
                    "Shed. A dispatch is stuck past the watchdog — inspect "
                    "engine.demotions and the active rung."
                )
        if drain:
            self.flush()

    def __enter__(self) -> "Engine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _flush_loop(self) -> None:
        interval = self.flush_interval_ms / 1e3
        margin = self.deadline_margin_ms / 1e3
        last_flush = time.perf_counter()
        while not self._stop.is_set():
            with self._cv:
                if not self._queue:
                    log = self._spans
                    t_w = time.perf_counter() if log is not None else 0.0
                    self._cv.wait(timeout=interval)
                    if log is not None:
                        log.add(spans.WAIT, 0, -1, t_w, time.perf_counter(), 0)
                    continue
                full = self._queue_frames >= self.group
                ddl = min(
                    (r.deadline_at for r in self._queue
                     if r.deadline_at is not None),
                    default=None,
                )
            now = time.perf_counter()
            due = (
                full
                or (ddl is not None and now >= ddl - margin)
                or (now - last_flush >= interval)
            )
            if due:
                try:
                    self.flush()
                except Exception:  # noqa: BLE001 — the loop must survive
                    _LOG.exception("background flush failed; loop continues")
                last_flush = time.perf_counter()
            else:
                wait = interval - (now - last_flush)
                if ddl is not None:
                    wait = min(wait, ddl - margin - now)
                log = self._spans
                with self._cv:
                    t_w = time.perf_counter() if log is not None else 0.0
                    self._cv.wait(timeout=max(1e-4, wait))
                    if log is not None:
                        log.add(spans.WAIT, 0, -1, t_w, time.perf_counter(), 1)
        # Drain whatever arrived before the stop signal.
        try:
            self.flush()
        except Exception:  # noqa: BLE001
            _LOG.exception("final drain flush failed")

    # -- spans ----------------------------------------------------------------

    def start_spans(self, capacity: int) -> spans.SpanLog:
        """Arm a fresh :class:`~repro.core.dhm.spans.SpanLog` of
        ``capacity`` rows: from the next flush on, the Engine records its
        host phases, queue waits, flush-loop waits and garbage
        collections there until :meth:`stop_spans`. While no log is
        armed, each span boundary costs one ``is None`` test."""
        if self._spans is not None:
            raise RuntimeError("a span log is already armed; stop_spans() first")
        log = spans.SpanLog(capacity)
        log.watch_gc()
        self._spans = log
        return log

    def stop_spans(self) -> spans.SpanLog:
        """Detach the armed span log and close it (its ``gc`` callback
        removed, its counts fixed); returns it."""
        log = self._spans
        if log is None:
            raise RuntimeError("no span log is armed")
        self._spans = None
        return log.close()

    # -- conveniences ----------------------------------------------------------

    def infer(self, x: jax.Array, *, deadline_ms: Optional[float] = None):
        """Convenience: submit + flush + result."""
        req = self.submit(x, deadline_ms=deadline_ms)
        if not self._flusher_alive():
            self.flush()
        return req.result()

    def stats(self) -> EngineStats:
        with self._lock:
            return EngineStats(
                n_requests=self._requests - self._requests_base,
                n_frames=self._frames,
                n_batches=self._batches,
                n_slots=self._slots,
                n_overlapped=self._overlapped,
                n_direct=self._direct,
                busy_s=self._busy_s,
                **dict(zip(_PHASES, self._phase_s)),
                mean_latency_s=(
                    self._lat_sum / self._lat_n if self._lat_n else 0.0
                ),
                max_latency_s=self._lat_max,
                n_ok=self._n_ok,
                n_rejected=self._n_rejected,
                n_shed=self._n_shed,
                n_deadline_exceeded=self._n_deadline,
                n_invalid=self._n_invalid,
                n_failed=self._n_failed,
                n_retries=self._n_retries,
                n_demotions=len(self.demotions),
                rung=self._rung_name,
                rung_latency_ms={
                    rung: {
                        "p50_ms": _percentile_ms(lat, 50.0),
                        "p99_ms": _percentile_ms(lat, 99.0),
                        "n": len(lat),
                    }
                    for rung, lat in self._rung_lat.items()
                },
            )

    def reset_stats(self) -> None:
        """Zero every counter and latency reservoir so a measurement run
        (load bench, SLO window) excludes warmup / prior-phase samples.
        The demotion ledger is kept — it is an audit trail, not a metric
        — and the queue and rung state are untouched."""
        with self._lock:
            self._requests_base = self._requests
            self._frames = 0
            self._batches = 0
            self._slots = 0
            self._overlapped = 0
            self._direct = 0
            self._busy_s = 0.0
            self._phase_s = [0.0] * len(_PHASES)
            self._lat_n = 0
            self._lat_sum = 0.0
            self._lat_max = 0.0
            self._rung_lat = {}
            self._n_ok = 0
            self._n_rejected = 0
            self._n_shed = 0
            self._n_deadline = 0
            self._n_invalid = 0
            self._n_failed = 0
            self._n_retries = 0
