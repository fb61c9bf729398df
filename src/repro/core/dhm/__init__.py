"""Direct Hardware Mapping (DHM) core — the paper's contribution, organised
as a compiler pipeline:

    CNNTopology --(graph)--> DPN actor graph --(mapping)--> stages
               --(compiler)--> CompiledDHM plan --(pipeline)--> mesh

- ``graph``: dataflow-process-network (DPN) IR; CNN/LM graph builders at the
  paper's actor granularity (conv engines, adder trees, activations).
- ``mapping``: exact min-max DP partitioning of the (topologically ordered)
  actor layers into contiguous stages — the TPU-native act of "direct
  mapping" (the FPGA's critical actor becomes the bottleneck stage).
- ``compiler``: the single lowering path. ``compile_dhm(topo, params,
  quant=QuantSpec(...), n_stages=..., backend=...)`` validates the
  topology, expands it to the DPN, partitions it from the actor FLOP
  payloads, and emits per-stage fused-kernel closures with quantization
  baked in (weights fake-quantized / pow2-projected once; the feature
  stream quantized inside the kernel epilogue; the FC head lowered through
  the packed pow2 matmul when requested). Every consumer — ``cnn_apply``,
  pipeline stage bodies, examples, e2e benchmarks — routes through it.
- ``pipeline``: the streaming pipelined executor (shard_map + ppermute);
  runs a CompiledDHM's stages on disjoint device groups, GPipe schedule.
  Heterogeneous stage geometries (pool/stride shrink, channel growth)
  stream over exact-shape ICI edge classes planned from the per-edge
  ``StageIOSpec`` the compiler emits (``plan_edges``; max-shape boxing is
  the fallback), optionally with double-buffered overlapped collectives;
  a 2D ``(stage, data)`` mesh adds batch sharding.
- ``engine``: where compiled plans execute — the eager/jitted forward
  paths, the mesh executor entry (``run_pipelined``), and the
  fault-tolerant serving ``Engine`` (continuous batching with deadline
  SLOs, bounded-queue admission control, watchdog + retry + a graceful
  degradation ladder, structured per-request errors).
- ``spans``: the ``Engine``'s span log — host phases, queue waits and
  garbage collections in preallocated columns, off unless armed
  (``Engine.start_spans``).
- ``faults``: deterministic, seed-driven fault injection (delayed flush,
  dispatch errors, stalled collectives, NaN activations, device loss)
  wired through ``Engine(fault_plan=...)`` for the chaos suite; fault
  windows can be scoped to one tenant for bulkhead testing.
- ``multitenant``: N compiled plans resident behind one ``Router`` —
  per-tenant queues/SLOs, deficit-round-robin weighted-fair scheduling,
  per-tenant circuit breakers, and verified hot plan swap with one-call
  rollback.
- ``resources``: the FPGA resource model for the three multiplier
  strategies (paper Tables 2 & 3).
- ``throughput``: the streaming-throughput model (paper Table 4) plus the
  spatial-pipeline cost model and the measurement-driven µbatch autotuner
  (``estimate_pipeline`` / ``fit_constants`` / ``autotune_pipeline``) that
  picks n_microbatches / batch grain / overlap per (plan, device count).
"""
from repro.core.dhm.compiler import (
    CompiledDHM,
    CompiledStage,
    PlanCheckError,
    QuantSpec,
    check_plan,
    compile_dhm,
    emit_conv_stage,
    validate_topology,
)
from repro.core.dhm.engine import (
    BatchFailed,
    DeadlineExceeded,
    Engine,
    EngineStats,
    FlusherWedged,
    InvalidRequest,
    LadderExhausted,
    Rejected,
    RequestError,
    Shed,
    run_pipelined,
)
from repro.core.dhm.spans import SpanLog
from repro.core.dhm.multitenant import (
    CircuitBreaker,
    CircuitOpen,
    Router,
    SwapRejected,
    UnknownTenant,
)
from repro.core.dhm.faults import (
    DelayedFlush,
    DeviceLoss,
    DispatchError,
    FaultPlan,
    InjectedDeviceLoss,
    InjectedDispatchError,
    InjectedFault,
    NaNActivation,
    StalledDispatch,
)
from repro.core.dhm.pipeline import (
    CollectiveTimeout,
    EDGE_MODES,
    EdgePlan,
    PipelineConfig,
    StageIOSpec,
    call_with_timeout,
    pipeline_forward,
    plan_edges,
)
from repro.core.dhm.graph import (
    Actor,
    ActorKind,
    DataflowGraph,
    cnn_to_dpn,
    layer_costs_to_dpn,
)
from repro.core.dhm.resources import (
    DeviceModel,
    CYCLONE_V_5CGXFC9E7,
    KINTEX7_XC7Z045,
    MultiplierStrategy,
    ResourceReport,
    estimate_resources,
)
from repro.core.dhm.throughput import (
    PipelineCostConstants,
    PipelineEstimate,
    PipelineTuning,
    ThroughputReport,
    autotune_pipeline,
    candidate_grid,
    dhm_throughput_gops,
    estimate_pipeline,
    fit_constants,
    load_sweep_measurements,
    pipeline_workload,
    streaming_throughput,
    sweep_sample,
)
from repro.core.dhm.mapping import StageAssignment, partition_stages, balance_report

__all__ = [
    "Actor",
    "ActorKind",
    "BatchFailed",
    "CollectiveTimeout",
    "CompiledDHM",
    "CompiledStage",
    "DataflowGraph",
    "DeadlineExceeded",
    "DelayedFlush",
    "DeviceLoss",
    "DispatchError",
    "CircuitBreaker",
    "CircuitOpen",
    "Engine",
    "EngineStats",
    "SpanLog",
    "FaultPlan",
    "FlusherWedged",
    "Router",
    "SwapRejected",
    "UnknownTenant",
    "InjectedDeviceLoss",
    "InjectedDispatchError",
    "InjectedFault",
    "InvalidRequest",
    "LadderExhausted",
    "NaNActivation",
    "EDGE_MODES",
    "EdgePlan",
    "PipelineConfig",
    "PipelineCostConstants",
    "PipelineEstimate",
    "PipelineTuning",
    "PlanCheckError",
    "QuantSpec",
    "Rejected",
    "RequestError",
    "Shed",
    "StageIOSpec",
    "StalledDispatch",
    "call_with_timeout",
    "check_plan",
    "pipeline_forward",
    "run_pipelined",
    "cnn_to_dpn",
    "compile_dhm",
    "emit_conv_stage",
    "layer_costs_to_dpn",
    "validate_topology",
    "DeviceModel",
    "CYCLONE_V_5CGXFC9E7",
    "KINTEX7_XC7Z045",
    "MultiplierStrategy",
    "ResourceReport",
    "estimate_resources",
    "dhm_throughput_gops",
    "ThroughputReport",
    "StageAssignment",
    "partition_stages",
    "balance_report",
    "autotune_pipeline",
    "candidate_grid",
    "estimate_pipeline",
    "fit_constants",
    "load_sweep_measurements",
    "pipeline_workload",
    "plan_edges",
    "streaming_throughput",
    "sweep_sample",
]
