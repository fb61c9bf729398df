"""Whole runs of the harness on the CPU.

A run refuses to measure without a TPU, and without the program beside
it. With the look for a chip skipped (``chip_checks=False``), a short run
at a size a test can hold serves the int8 configuration through the
program's Engine, and ``correct`` comes out true; with the timed path
broken underneath, it comes out false, once for each fault a classifier
server can have.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import registry

SMALL_BULK = {
    "clients": 2, "frames_per_request": 8, "pool_frames": 64,
    "warmup_s": 0.2, "engine": {"microbatch": 16},
}
ARGS = ["--workload", "cifar10_int8.bulk", "--seed", str(2**31 + 5),
        "--seconds", "0.5", "--trace", "0"]


def _cmd(cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _cmd(registry.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    p = _cmd(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.run`` on the CPU, without the persistent compile cache."""
    import jax

    import run

    monkeypatch.setattr(run, "_jax", lambda: jax)

    def go(**kw):
        return run.run(ARGS, chip_checks=False, traffic_overrides=SMALL_BULK, **kw)

    return go


def _break_forward(monkeypatch, fault):
    """Wrap the Engine's jitted forward so that what it returns carries
    ``fault`` on every micro-batch."""
    from repro.core.dhm import engine

    real = engine.plan_jitted_forward

    def broken(plan, *, donate=False):
        fwd = real(plan, donate=donate)

        def call(xb):
            return fault(fwd(xb))

        return call

    monkeypatch.setattr(engine, "plan_jitted_forward", broken)


def test_sound_run_is_correct(cpu_run):
    out = cpu_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"frames_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


@pytest.mark.parametrize("fault", ["half_batch_left_out", "answer_altered"])
def test_broken_timed_path_is_not_correct(cpu_run, monkeypatch, fault):
    def half(y):  # half of each micro-batch never computed
        return y.at[y.shape[0] // 2:].set(0.0)

    def altered(y):  # one logit of one frame changed where it is produced
        return y.at[0, 3].add(0.5)

    _break_forward(monkeypatch, half if fault == "half_batch_left_out" else altered)
    out = cpu_run()
    assert not out["correct"], out["checks"]


def test_percentile_is_nearest_rank():
    from pct import nearest_rank

    xs = list(range(1, 101))
    assert nearest_rank(xs, 95.0) == 95
    assert nearest_rank([3.0, float("inf")], 95.0) == float("inf")
    assert nearest_rank([], 95.0) is None


def test_open_loop_schedule_keeps_its_count_across_seeds():
    """The open loop's Poisson arrivals: the same count for every seed,
    sorted inside the window, in an order the seed changes."""
    import inputs

    open_loop = registry.traffic_loop("open")
    poisson = {"rate_per_s": 100}
    a = open_loop.schedule(poisson, 2.0, inputs.streams(2**40 + 3)["traffic"])
    b = open_loop.schedule(poisson, 2.0, inputs.streams(7)["traffic"])
    assert len(a) == len(b) == 200 and np.all(np.diff(a) >= 0)
    assert 0.0 <= a.min() and a.max() < 2.0 and not np.array_equal(a, b)


def test_open_loop_sends_each_request_at_its_arrival():
    """Driven against a stand-in server, the open loop sends one request
    due at each scheduled arrival, keeps every one, and sees each answer."""
    import concurrent.futures
    import time

    import loadgen

    class Answered(concurrent.futures.Future):
        def __init__(self, frames):
            super().__init__()
            self.set_result(frames)

    traffic = {"loop": "open", "rate_per_s": 40, "frames_per_request": 1,
               "pool_frames": 8}
    pool = np.arange(8, dtype=np.float32)
    starts = loadgen.request_starts(traffic, np.random.default_rng(0))
    load = loadgen.Load(traffic, Answered, pool, starts, np.random.default_rng(1))
    t0 = time.perf_counter()
    load.start(t0, 0.5)
    load.join()
    want = registry.traffic_loop("open").schedule(
        traffic, 0.5, np.random.default_rng(1))
    assert len(load.sent) == 20 and all(s.ok for s in load.sent)
    np.testing.assert_allclose([s.due - t0 for s in load.sent], want, atol=1e-9)
    assert all(s.sent >= s.due for s in load.sent)
    assert all(float(s.logits) == s.first for s in load.sent)


def test_failed_requests_count_as_the_longest_wait():
    """A request answered with an error counts as ``GRACE_S`` late: with
    one in ten failed the p95 reads that wait, and the run still has a
    number to report."""
    import loadgen

    sent = []
    for i in range(100):
        s = loadgen.Sent(float(i), 0, 1)
        s.seen, s.ok = float(i) + 0.010, i % 10 != 0
        sent.append(s)

    class Ctx:
        t0, t_end = 0.0, 100.0

        @staticmethod
        def due_in(a, b):
            return [s for s in sent if a <= s.due < b]

    reader = registry.metric_reader("latency_p95_ms")
    assert reader.read(Ctx) == loadgen.GRACE_S * 1e3
    for s in sent:
        s.ok = True
    assert reader.read(Ctx) == pytest.approx(10.0)
