"""End-to-end compiled-plan throughput (the per-PR Table-4 analogue).

For each benchmarked topology — the three paper nets plus the generalized
non-paper ones (cifar10_full: overlapping 3x3/stride-2 pool;
cifar10_strided: stride-2 downsampling convs) — lower a full plan through
``compile_dhm`` (the single lowering path everything routes through)
twice per quantization variant (fp32, fake-quant at the paper bitwidths,
and ``int8`` — the true-integer compute path, whose fused row carries
``int8_speedup`` vs the fp32 fused plan plus the dtype-aware fusion
widening probe fields):

- the **fused** plan (default VMEM budget): the feature extractor runs as
  cross-layer fusion groups — one fused pyramid kernel per group, with
  inter-layer feature slabs kept on-chip;
- the **per-layer** plan (``vmem_budget=0``): today's pre-fusion baseline,
  one kernel call per conv layer with every intermediate feature map
  round-tripping through memory.

Both execute through the plan's cached end-to-end jitted closure
(``CompiledDHM.__call__``), so the comparison isolates the fusion
decision, and both rows land in ``BENCH_kernels.json`` — the fused row
carries ``fusion_speedup`` vs its per-layer twin. After timing, the
benchmark asserts the plan never retraced across reps (the jit cache
holds exactly one entry).

Two more row families measure the SPATIAL pipeline on a multi-device
``(stage, data)`` mesh. On the CPU the device count must be forced before
JAX initializes, so both are measured in one subprocess —
``python -m benchmarks.e2e_bench --pipelined-json --handoff <npz>`` with
``--xla_force_host_platform_device_count=8``; on an accelerator they are
measured in this process, on its real devices:

- ``path: pipeline_sweep`` — the µbatch/batch-grain crossover sweep:
  cifar10 and svhn fp32 across (n_microbatches, grain, overlap) configs,
  each row carrying its config fields + ``pipeline_speedup``. These rows
  are what ``throughput.fit_constants`` / ``autotune_pipeline`` consume
  from ``BENCH_history.jsonl``.
- ``path: e2e_pipelined`` — every (topology, precision) served through
  the ``Engine`` at the configuration the measurement-driven autotuner
  picked (measured sweep points outrank the fitted cost model), logits
  verified and ``pipeline_speedup`` recorded vs the single-device plan.

The single-device references (logits + frames/s per group size) are
measured ONCE in the main process and handed over as an ``.npz`` file —
the subprocess never recompiles or re-runs the reference plan.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core.dhm.compiler import QuantSpec, compile_dhm
from repro.models.cnn import ALL_TOPOLOGIES, init_cnn

# Paper bit-widths (Table 3): 3 bits LeNet5, 6 bits Cifar10/SVHN; the
# non-paper Cifar10 variants inherit the Cifar10 regime.
PAPER_BITS = {
    "lenet5": 3, "cifar10": 6, "svhn": 6,
    "cifar10_full": 6, "cifar10_strided": 6,
}
BATCH = 8
PIPE_TOPOS = ("lenet5", "cifar10", "svhn", "cifar10_full", "cifar10_strided")
# Group sizes (frames per engine dispatch) the pipelined paths may use;
# the main process pre-measures the single-device reference at each.
PIPE_GROUPS = (32, 64, 128, 256)
# The crossover sweep: (n_microbatches, batch grain, overlap) on a
# (3 stage x 2 data) mesh for the two paper CNNs with 3 conv layers.
SWEEP_TOPOS = ("cifar10", "svhn")
SWEEP_GRID = (
    (2, 16, False),
    (4, 16, False),
    (8, 16, False),
    (4, 32, False),
    (8, 32, False),
    (4, 32, True),  # overlapped-collective point: records the crossover
)


def _stages_of(name: str) -> int:
    return min(3, len(ALL_TOPOLOGIES[name].conv_layers))


def _pipe_input(name: str, group: int) -> np.ndarray:
    """Deterministic input frames shared by the main process (reference)
    and the mesh subprocess (pipelined runs) — numpy RNG, so the two
    processes agree bit-for-bit without shipping the arrays."""
    topo = ALL_TOPOLOGIES[name]
    h, w = topo.input_shape
    rng = np.random.RandomState(1)
    return rng.standard_normal(
        (group, h, w, topo.input_channels)
    ).astype(np.float32)


def _time(fn, *args, reps=10, passes=3):
    """Best-of-``passes`` timing (each pass averages ``reps`` calls), so
    the recorded per-PR trajectory reflects the achievable rate rather
    than scheduler noise on a shared machine. Every rep blocks on its own
    output: with only the last rep blocked, JAX's async dispatch overlaps
    host-side dispatch of rep i+1 with device execution of rep i and the
    per-call latency under-reports. ``jax.block_until_ready`` (not the
    array method) so callables that hand back host numpy — e.g. the
    serving Engine, which packs and scatters batches host-side — time
    the same way as device-array producers."""
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(passes):
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        best = min(best, (time.time() - t0) / reps * 1e6)
    return best


def _measure_plan(plan, x):
    """us/call through the plan's cached jitted closure, asserting the
    closure never retraces across reps."""
    us = _time(plan, x)
    fwd = plan.jitted_forward()
    n_traces = fwd._cache_size()
    assert n_traces == 1, (
        f"plan retraced across reps: jit cache holds {n_traces} entries"
    )
    return us


def _write_handoff(plans: dict, path: str) -> None:
    """Measure the single-device reference ONCE per (topology, precision,
    group size) — logits + frames/s — and save it for the mesh
    subprocess, which must never recompile the reference plan. Called
    after ``_measure_plan`` has already asserted the no-retrace invariant
    at the e2e batch (these extra shapes legitimately add cache entries)."""
    blobs = {}
    for (name, label), plan in plans.items():
        for g in PIPE_GROUPS:
            x = _pipe_input(name, g)
            us = _time(plan, x, reps=3, passes=2)
            blobs[f"{name}|{label}|{g}|ref"] = np.asarray(plan(x))
            blobs[f"{name}|{label}|{g}|fps"] = np.float64(g / (us * 1e-6))
    np.savez(path, **blobs)


def _mesh_logits_fn(plan, mesh, cfg, n_microbatches, microbatch):
    """The raw pipelined logits closure (runner + FC head as one jitted
    computation) used by the sweep — the serving Engine adds host-side
    batching on top; the sweep prices the pipeline itself."""
    from repro.core.dhm.engine import build_plan_pipeline

    runner = build_plan_pipeline(
        plan, mesh=mesh, cfg=cfg, microbatch=microbatch
    )

    def _fwd(leaves, frames):
        mbs = frames.reshape(
            (n_microbatches, microbatch) + frames.shape[1:]
        )
        feats = runner.apply(leaves, mbs)
        flat = feats.reshape(
            (n_microbatches * microbatch,) + feats.shape[2:]
        )
        return plan.head_fn(flat)

    fjit = jax.jit(_fwd)
    return lambda frames: fjit(runner.stacked_leaves, frames), runner


def _sweep_rows_here(handoff) -> list:
    """The ``path: pipeline_sweep`` rows: the µbatch/grain crossover for
    the sweep topologies, each point verified against the pre-measured
    single-device logits and stamped with its full configuration (these
    rows are the autotuner's measurement source)."""
    from repro.core.dhm.pipeline import PipelineConfig, make_pipeline_mesh

    rows = []
    for name in SWEEP_TOPOS:
        topo = ALL_TOPOLOGIES[name]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        S = _stages_of(name)
        data = 2
        mesh = make_pipeline_mesh((S, data), ("stage", "data"))
        plan = compile_dhm(topo, params, n_stages=S)
        for M, mb, overlap in SWEEP_GRID:
            group = M * mb
            cfg = PipelineConfig(
                S, M, data_axis="data", overlap=overlap, edge_mode="auto"
            )
            fn, runner = _mesh_logits_fn(plan, mesh, cfg, M, mb)
            x = _pipe_input(name, group)
            got = np.asarray(fn(x))
            ref = handoff[f"{name}|fp32|{group}|ref"]
            assert np.allclose(got, ref, rtol=1e-4, atol=1e-4), (
                f"{name} sweep M={M} mb={mb} overlap={overlap}: "
                f"pipelined logits diverge from single-device"
            )
            us = _time(fn, x, reps=3, passes=2)
            fps = group / (us * 1e-6)
            fps_single = float(handoff[f"{name}|fp32|{group}|fps"])
            tag = f"M{M}x{mb}" + ("_ov" if overlap else "")
            rows.append(
                {
                    "name": f"e2e/{name}_fp32_sweep_{tag}",
                    "us_per_call": us,
                    "path": "pipeline_sweep",
                    "topology": name,
                    "label": "fp32",
                    "n_stages": S,
                    "n_microbatches": M,
                    "microbatch": mb,
                    "data": data,
                    "overlap": overlap,
                    "edge_mode": "auto",
                    "edge_path": runner.edge_plan.mode,
                    "frames_per_s": fps,
                    "pipeline_speedup": fps / fps_single,
                    "derived": (
                        f"{fps:.0f} frames/s sweep point ({M}x{mb}-frame "
                        f"groups, data={data}, "
                        f"{'overlapped' if overlap else 'serial'} schedule, "
                        f"{runner.edge_plan.mode} edges): "
                        f"x{fps / fps_single:.2f} vs single-device "
                        f"({fps_single:.0f} frames/s)"
                    ),
                }
            )
    return rows


def _pipelined_rows_here(handoff_path: str) -> list:
    """Measure the pipelined serving rows IN THIS PROCESS (requires a
    multi-device backend — the subprocess entry below forces 8 host
    devices): first the crossover sweep, then every topology through the
    ``Engine`` at the configuration the autotuner picked from the sweep.
    Single-device references come from the handoff file — nothing is
    recompiled here."""
    from repro.core.dhm.engine import Engine
    from repro.core.dhm.throughput import (
        autotune_pipeline, fit_constants, sweep_sample,
    )

    handoff = dict(np.load(handoff_path))
    n_dev = len(jax.devices())
    need = max(_stages_of(n) for n in PIPE_TOPOS) * 2
    if n_dev < need:
        raise RuntimeError(
            f"pipelined bench needs {need} {jax.default_backend()} devices, "
            f"have {n_dev}"
        )
    rows = _sweep_rows_here(handoff)
    sweep_rows = list(rows)

    # Fit the machine constants (FLOP/s, bytes/s, tick overhead) from the
    # measured serial sweep points — they are topology-independent, so
    # the un-swept topologies get model-tuned with measured constants.
    sweep_plans = {}
    samples = []
    for name in SWEEP_TOPOS:
        topo = ALL_TOPOLOGIES[name]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        sweep_plans[name] = compile_dhm(
            topo, params, n_stages=_stages_of(name)
        )
    for r in sweep_rows:
        samples.append(
            sweep_sample(
                sweep_plans[r["topology"]],
                n_microbatches=r["n_microbatches"],
                microbatch=r["microbatch"],
                data=r["data"],
                frames_per_s=r["frames_per_s"],
                overlap=r["overlap"],
                edge_mode=r["edge_mode"],
            )
        )
    constants = fit_constants(samples)

    for name in PIPE_TOPOS:
        topo = ALL_TOPOLOGIES[name]
        bits = PAPER_BITS[name]
        S = _stages_of(name)
        data = n_dev // S
        params = init_cnn(jax.random.PRNGKey(0), topo)
        mesh = make_pipeline_mesh((S, data), ("stage", "data"))
        for label, quant in (
            ("fp32", QuantSpec()),
            ("quant", QuantSpec(weight_bits=bits, act_bits=bits)),
        ):
            plan = compile_dhm(topo, params, quant=quant, n_stages=S)
            measured = [
                r for r in sweep_rows
                if r["topology"] == name and r["label"] == label
            ]
            tuning = autotune_pipeline(
                plan, n_dev,
                measurements=measured,
                constants=constants,
                microbatches=(2, 4, 8),
                grains=(16, 32),
                overlaps=(False,),
            )
            if measured:
                # The acceptance contract: with a sweep on record the
                # tuner's choice is within 20% of the best measured point.
                best_fps = max(r["frames_per_s"] for r in measured)
                assert tuning.frames_per_s >= 0.8 * best_fps, (
                    f"{name}/{label}: tuner picked "
                    f"{tuning.frames_per_s:.0f} frames/s, best measured "
                    f"{best_fps:.0f}"
                )
            group = tuning.n_microbatches * tuning.microbatch
            eng = Engine(plan, mesh=mesh, data_axis="data", tuning=tuning)
            assert eng.group == group
            x = _pipe_input(name, group)
            got = eng.infer(x)
            ref = handoff[f"{name}|{label}|{group}|ref"]
            assert np.allclose(
                np.asarray(got), ref, rtol=1e-4, atol=1e-4
            ), f"{name}/{label}: pipelined logits diverge from single-device"
            us = _time(eng.infer, x, reps=5, passes=2)
            fps = group / (us * 1e-6)
            fps_single = float(handoff[f"{name}|{label}|{group}|fps"])
            edge_path = eng._runner.edge_plan.mode
            bits_fields = (
                {"weight_bits": bits, "act_bits": bits}
                if label == "quant"
                else {}
            )
            rows.append(
                {
                    "name": f"e2e/{name}_{label}_pipelined_plan",
                    "us_per_call": us,
                    "path": "e2e_pipelined",
                    "frames_per_s": fps,
                    "pipeline_speedup": fps / fps_single,
                    **bits_fields,
                    "n_microbatches": tuning.n_microbatches,
                    "microbatch": tuning.microbatch,
                    "tuning_source": tuning.source,
                    "edge_path": edge_path,
                    "derived": (
                        f"{fps:.0f} frames/s through the serving Engine on "
                        f"a ({S} stage x {data} data) "
                        f"{jax.default_backend()} mesh "
                        f"({tuning.n_microbatches}x{tuning.microbatch}"
                        f"-frame groups autotuned [{tuning.source}], "
                        f"{edge_path} ICI edges): x{fps / fps_single:.2f} "
                        f"vs the single-device plan ({fps_single:.0f} "
                        f"frames/s), logits verified equal"
                    ),
                }
            )
    return rows


def run_pipelined() -> list:
    """The ``path: pipeline_sweep`` + ``path: e2e_pipelined`` rows. The
    single-device references are measured HERE first and handed off.

    On the CPU backend the rows are measured in a subprocess with 8
    forced host-platform devices (the flag must be set before JAX
    initializes, and this process may be single-device); the subprocess
    never runs the reference plan. On an accelerator this process holds
    the chips — a child could not reach them — so the rows are measured
    here, on the real devices, and a host with too few raises."""
    repo_root = pathlib.Path(__file__).resolve().parents[1]

    # Reference pass: one plan per (topology, precision), measured at
    # every candidate group size.
    plans = {}
    for name in PIPE_TOPOS:
        topo = ALL_TOPOLOGIES[name]
        bits = PAPER_BITS[name]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        for label, quant in (
            ("fp32", QuantSpec()),
            ("quant", QuantSpec(weight_bits=bits, act_bits=bits)),
        ):
            plans[(name, label)] = compile_dhm(
                topo, params, quant=quant, n_stages=_stages_of(name)
            )

    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(repo_root / "src")
        + (os.pathsep + os.environ["PYTHONPATH"]
           if os.environ.get("PYTHONPATH") else ""),
    }
    with tempfile.TemporaryDirectory() as td:
        handoff = os.path.join(td, "single_device_refs.npz")
        _write_handoff(plans, handoff)
        if jax.default_backend() != "cpu":
            return _pipelined_rows_here(handoff)
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.e2e_bench",
             "--pipelined-json", "--handoff", handoff],
            capture_output=True, text=True, env=env, cwd=str(repo_root),
            timeout=1800,
        )
    if res.returncode != 0:
        raise RuntimeError(
            "pipelined benchmark subprocess failed:\n" + res.stderr[-3000:]
        )
    # The rows are the last stdout line (JAX may log above them).
    return json.loads(res.stdout.strip().splitlines()[-1])


def run() -> list:
    from repro.core.dhm.fusion import widening_budget

    rows = []
    for name in PIPE_TOPOS:
        topo = ALL_TOPOLOGIES[name]
        bits = PAPER_BITS[name]
        params = init_cnn(jax.random.PRNGKey(0), topo)
        h_in, w_in = topo.input_shape
        x = jax.random.normal(
            jax.random.PRNGKey(1),
            (BATCH, h_in, w_in, topo.input_channels),
        )
        variants = (
            ("fp32", QuantSpec()),
            ("quant", QuantSpec(weight_bits=bits, act_bits=bits)),
            (
                "int8",
                QuantSpec(
                    weight_bits=bits, act_bits=bits, int8_compute=True
                ),
            ),
        )
        fused_fps = {}
        for label, quant in variants:
            plan = compile_dhm(topo, params, quant=quant)
            plan_pl = compile_dhm(topo, params, quant=quant, vmem_budget=0)
            us_pl = _measure_plan(plan_pl, x)
            us = _measure_plan(plan, x)
            fps = BATCH / (us * 1e-6)
            fps_pl = BATCH / (us_pl * 1e-6)
            fused_fps[label] = fps
            gops = topo.feature_extractor_ops() * fps / 1e9
            speedup = us_pl / us
            qdesc = {
                "fp32": "fp32",
                "quant": f"w{bits}b + in-kernel act{bits}b stream quant",
                "int8": (
                    f"true int8 compute: w{bits}b codes, int32 accumulate, "
                    f"requantizing act{bits}b epilogue"
                ),
            }[label]
            gdesc = "+".join(
                str(len(g.layers)) for g in plan.fusion_groups
            )
            # DPN boundary streams of the fused interior layer edges: the
            # inter-layer pixel traffic that no longer crosses external
            # memory (DPN layer i+1 is conv layer i; layer 0 the source).
            onchip = sum(
                plan.graph.boundary_stream_bytes(li + 1)
                for g in plan.fusion_groups
                for li in g.layers[:-1]
            )
            fused_row = {
                "name": f"e2e/{name}_{label}_plan",
                "us_per_call": us,
                "path": f"e2e_{label}",
                "frames_per_s": fps,
                "fusion_speedup": speedup,
                "derived": (
                    f"{fps:.0f} frames/s ({gops:.2f} effective Gop/s) "
                    f"for the full compiled plan (batch={BATCH}, "
                    f"{qdesc}, fused groups [{gdesc} layers/kernel] + "
                    f"FC head, one jitted closure): x{speedup:.2f} vs "
                    f"per-layer stages, {onchip / 1024:.0f} KiB/frame "
                    f"of inter-layer streams stay on-chip"
                ),
            }
            perlayer_row = {
                "name": f"e2e/{name}_{label}_perlayer_plan",
                "us_per_call": us_pl,
                "path": f"e2e_{label}_perlayer",
                "frames_per_s": fps_pl,
                "derived": (
                    f"{fps_pl:.0f} frames/s pre-fusion baseline "
                    f"(vmem_budget=0: one kernel call per conv layer, "
                    f"intermediates round-trip through memory)"
                ),
            }
            if label != "fp32":
                for row in (fused_row, perlayer_row):
                    row["weight_bits"] = bits
                    row["act_bits"] = bits
            if label == "int8":
                int8_speedup = fps / fused_fps["fp32"]
                fused_row["int8_speedup"] = int8_speedup
                # Dtype-aware fusion widening: the budget (1 B under the
                # cheapest fp32 whole-run cost) at which int8 slab costing
                # fuses a strictly larger group than fp32 costing.
                probe = widening_budget(
                    topo, tuple(range(len(topo.conv_layers)))
                )
                if probe is not None:
                    fused_row["widening_budget"] = probe["budget"]
                    fused_row["fp32_max_group"] = probe["fp32_max_group"]
                    fused_row["int8_max_group"] = probe["int8_max_group"]
                fused_row["derived"] += (
                    f"; x{int8_speedup:.2f} vs the fp32 fused plan"
                )
                if probe is not None:
                    fused_row["derived"] += (
                        f"; at a {probe['budget']}-B budget int8 slab "
                        f"costing fuses {probe['int8_max_group']} layers "
                        f"where fp32 fits {probe['fp32_max_group']}"
                    )
            rows.append(fused_row)
            rows.append(perlayer_row)
    rows += run_pipelined()
    return rows


if __name__ == "__main__":
    if "--pipelined-json" in sys.argv:
        # Subprocess entry: this process was launched with 8 forced host
        # devices; emit the sweep + pipelined rows as one JSON line on
        # stdout, reading single-device references from the handoff file.
        handoff_path = sys.argv[sys.argv.index("--handoff") + 1]
        print(json.dumps(_pipelined_rows_here(handoff_path)))
    else:
        for r in run():
            print(r["name"], "|", f"{r['us_per_call']:.1f}us", "|", r["derived"])
