"""Run one benchmark cell once, on the chip, and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) is one configuration under one
traffic mix. The run makes the weights and frames from the seed, compiles
the configuration with the program's ``compile_dhm``, serves it behind the
program's ``Engine`` with the background flusher, drives the traffic mix
for ``--seconds``, then checks every answer against the plain reference.

It refuses to measure anything but the DHM path on a TPU: no TPU, fewer
chips than the cell asks for, a compiled forward whose Mosaic kernels
(``tpu_custom_call``) do not match the plan's fusion groups, an Engine that
served on a rung other than ``fused`` or recorded a demotion, or a
compilation inside the window each end the run with a non-zero exit and no
result line.

With ``--trace 0`` the result carries the cell's end-to-end metrics. With
``--trace 1`` it carries the per-layer metrics: the profiler records the
last ``TRACE_S`` seconds of the window on the device alone, the trace
gives the device metrics and the breakdown, and the metrics taken on the
host clock or from the Engine's counters come from the untraced part
before it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up runs from here to the window

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import check  # noqa: E402
import inputs  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import registry  # noqa: E402
import tracereduce  # noqa: E402

TRACE_S = 3.0  # seconds at the end of a traced window that the profiler records
CACHE_DIR = registry.BENCH / ".jax_cache"
TRACE_DIR = registry.BENCH / ".traces"


class RunFailed(RuntimeError):
    """The run cannot measure the cell; it prints no result."""


class Context:
    """What a metric reader (``metrics/<name>.py``) may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def answered_frames(self, t_from: float, t_to: float) -> int:
        """Frames of requests answered with logits and seen in [from, to)."""
        return sum(
            s.n for s in self.sent
            if s.ok and s.seen is not None and t_from <= s.seen < t_to
        )

    def due_in(self, t_from: float, t_to: float) -> list:
        return [s for s in self.sent if t_from <= s.due < t_to]


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _jax():
    """JAX with its persistent compilation cache at the checkout's fixed
    ``CACHE_DIR``, caching every program however fast it compiled, and
    with no size limit: the limit's eviction keeps an access-time file
    beside each entry, and one entry left without it (a run killed while
    writing) makes every later write fail. The TPU runtime, which starts
    after this, logs to a fixed /tmp path unless told otherwise; a run
    writes nothing outside its checkout."""
    import jax

    os.environ.setdefault("TPU_LOG_DIR", str(registry.BENCH / ".tpu_logs"))
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class CompileCounter:
    """Counts executables obtained (compiled, or read from the persistent
    cache) while it is armed."""

    def __init__(self, jax):
        from jax import monitoring

        self.n, self.armed = 0, False
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, event, duration, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _system(cfg: dict, params):
    """The system under test: the configuration compiled by the program."""
    from repro.core.dhm import QuantSpec, compile_dhm
    from repro.models.cnn import CNNTopology, ConvLayerSpec

    t = cfg["topology"]
    topo = CNNTopology(
        name=cfg["name"],
        input_hw=tuple(t["input_hw"]),
        input_channels=t["input_channels"],
        conv_layers=tuple(ConvLayerSpec(**layer) for layer in t["conv_layers"]),
        fc_dims=tuple(t["fc_dims"]),
        n_classes=t["n_classes"],
    )
    return compile_dhm(topo, params, quant=QuantSpec(**cfg["quant"]))


def _count_kernels(jax, plan, group: int, topology: dict) -> int:
    h, w = topology["input_hw"]
    x = jax.ShapeDtypeStruct((group, h, w, topology["input_channels"]), np.float32)
    text = plan.jitted_forward(donate=True).lower(x).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _check_rung(engine) -> None:
    if engine.rung != "fused" or engine.demotions:
        raise RunFailed(
            f"Engine served on rung {engine.rung!r} with demotions "
            f"{engine.demotions}: not the DHM fused path"
        )


def bench_window_mark(x):
    """A device op too small to matter, run at both ends of the device
    trace: its module's runs mark the window on the device's clock."""
    return x + 1


def _profile(jax, out_dir, until: float, window_mark) -> str:
    """Profile the device until ``until``, with a run of ``window_mark``
    at both ends; returns the ``.xplane.pb`` written. Host events and the
    Python tracer stay off: they slow the Engine's host path, whose time
    the window measures (``tracereduce``)."""
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        window_mark()
        time.sleep(max(0.0, until - time.perf_counter()))
        window_mark()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    if not found:
        raise RunFailed(f"the profiler wrote no .xplane.pb under {out_dir}")
    return found[0]


def _compare(sent, pool, params, cfg) -> tuple:
    """Reference logits for the pool frames the answered requests carry,
    and the compared numbers."""
    ok = [s for s in sent if s.ok]
    if not ok:
        return {k: float("inf") for k in cfg["limits"] if k != "unanswered"}
    idx = np.concatenate([np.arange(s.first, s.first + s.n) for s in ok])
    served = np.concatenate([np.asarray(s.logits).reshape(s.n, -1) for s in ok])
    used, inv = np.unique(idx, return_inverse=True)
    ref = reference.logits(params, pool[used], cfg["topology"], **cfg["reference"])
    return check.numbers(served, ref[inv])


def run(argv=None, *, chip_checks: bool = True, traffic_overrides=None) -> dict:
    """One run of one cell; returns the result line's object. Raises
    ``RunFailed`` where the run cannot measure the cell.

    ``chip_checks=False`` skips the look for a TPU and the kernel count,
    and ``traffic_overrides`` replaces keys of the traffic file: both are
    for the harness's own tests, which drive a run on the CPU at a size a
    test can hold."""
    args = _args(argv)
    bm = registry.benchmark()
    cell = registry.cell(bm, args.workload)
    cfg = registry.config(bm, cell["config"])
    traffic = {**registry.traffic(cell["traffic"]), **(traffic_overrides or {})}
    topology = cfg["topology"]

    jax = _jax()
    devices = jax.devices()
    if chip_checks and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        raise RunFailed(
            f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform} device(s)"
        )
    counter = CompileCounter(jax)
    marks = [("start_jax", time.perf_counter())]

    rs = inputs.streams(args.seed)
    params = inputs.make_params(topology, rs["weights"])
    pool = inputs.frame_pool(
        topology, cfg["frame_grid_bits"], traffic["pool_frames"], rs["frames"]
    )
    starts = loadgen.request_starts(traffic, rs["traffic"])
    jax.block_until_ready(params)
    marks.append(("inputs", time.perf_counter()))

    from repro.core.dhm import Engine

    plan = _system(cfg, params)
    marks.append(("compile_dhm", time.perf_counter()))
    group = traffic["engine"]["microbatch"]
    if chip_checks:
        n_kernels = _count_kernels(jax, plan, group, topology)
        n_groups = len(plan.fusion_groups)
        print(f"tpu_custom_call={n_kernels} fusion_groups={n_groups}", flush=True)
        if n_kernels != n_groups:
            raise RunFailed(
                f"{n_kernels} tpu_custom_call in the compiled forward for "
                f"{n_groups} fusion groups: not the DHM kernel path"
            )
    marks.append(("kernel_count", time.perf_counter()))
    engine = Engine(plan, **traffic["engine"])
    engine.start()
    one = jax.numpy.zeros((1,), np.float32)
    mark_fn = jax.jit(bench_window_mark)

    def window_mark():
        jax.block_until_ready(mark_fn(one))

    window_mark()  # compiled here, in set-up
    marks.append(("engine", time.perf_counter()))
    try:
        warm = loadgen.Load(traffic, engine.submit, pool, starts, rs["traffic"])
        warm.start(time.perf_counter(), traffic["warmup_s"])
        warm.join()
        _check_rung(engine)
        del warm
        marks.append(("warmup_traffic", time.perf_counter()))
        prev, parts = T_PROCESS, []
        for name, m in marks:
            parts.append(f"{name}={m - prev:.3f}")
            prev = m
        print("setup_s " + " ".join(parts), file=sys.stderr, flush=True)

        load = loadgen.Load(traffic, engine.submit, pool, starts, rs["traffic"])
        engine.reset_stats()
        counter.armed = True
        t0 = time.perf_counter()
        setup_s = t0 - T_PROCESS
        t_end = t0 + args.seconds
        load.start(t0, args.seconds)
        trace_path = None
        if args.trace:
            t_split = t_end - min(TRACE_S, args.seconds / 2)
            time.sleep(max(0.0, t_split - time.perf_counter()))
            host_stats = engine.stats()
            t_split = time.perf_counter()
            trace_path = _profile(jax, TRACE_DIR / cell["name"], t_end, window_mark)
        else:
            time.sleep(max(0.0, t_end - time.perf_counter()))
            host_stats = engine.stats()
            t_split = time.perf_counter()
        load.join()
        counter.armed = False
        _check_rung(engine)
        print(f"compiles_in_window={counter.n}", flush=True)
        if counter.n:
            raise RunFailed(f"{counter.n} compilation(s) inside the measured window")
    finally:
        engine.stop()
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    del engine, plan
    gc.collect()

    sent = load.sent
    ctx = Context(
        cell=cell, cfg=cfg, traffic=traffic, seconds=args.seconds,
        t0=t0, t_end=t_end, t_split=t_split, setup_s=setup_s, sent=sent,
        host_stats=host_stats, device_kind=devices[0].device_kind,
        trace=tracereduce.read(trace_path) if trace_path else None,
    )
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in registry.cell_metrics(bm, cell["name"], kind):
        value = registry.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    unanswered = sum(1 for s in sent if s.seen is None)
    nums = _compare(sent, pool, params, cfg)
    nums["unanswered"] = unanswered
    correct, checks = check.verdict(nums, cfg["limits"])
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }
    out = {
        "correct": correct,
        "attempted": len(sent),
        "failed": sum(1 for s in sent if not s.ok),
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
        ops = sorted(ctx.trace.op_seconds().items(), key=lambda kv: -kv[1])
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in ops[:10]],
            "idle_gaps": ctx.trace.idle_gaps(10),
        }
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except RunFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    bad = [k for k, m in out["metrics"].items() if not np.isfinite(m["value"])]
    if bad:
        print(f"FAIL: no finite value for {bad}: {out['failed']} of "
              f"{out['attempted']} requests failed", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        if not np.isfinite(c["value"]):
            c["value"] = str(c["value"])  # an infinite gap, kept valid JSON
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(registry.ROOT / "src"))
    sys.exit(main())
