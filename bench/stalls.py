"""Look for what stalls a run: one run of a cell, watched from inside.

    python bench/stalls.py --workload cifar10_int8.bulk --seed 7 --seconds 30

Runs the cell once as ``run.py`` makes it, with a watcher thread beside
it that wakes every ``TICK_S``. A wake-up that comes more than ``LATE_S``
late is a stall of the whole interpreter (the watcher could not run):
it records when, how long, and the innermost frames of every Python
thread as the stall ended. Every ``STACK_S`` it keeps those frames too,
so that the longest stretch of the window in which no answer came can be
laid against what each thread was doing through it. Every ``SAMPLE_S``
it also reads the CPU
seconds of each thread of the process (``/proc/self/task``, named by
the Python thread or the runtime's own name) and the machine's stolen
and I/O-wait time (``/proc/stat``). After the run it prints one JSON
line: the run's result, frames or requests answered per second of the
window, the longest stalls, the longest wait between two answers with
the frames each thread sat in through it, the busiest threads, and the
stolen time per second. A diagnostic only: the benchmark's own runs never watch
themselves.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import resource
import sys
import threading
import time
import traceback

import numpy as np

import registry
import run

TICK_S = 0.002
LATE_S = 0.010
SAMPLE_S = 0.5
STACK_S = 0.05
CLK = os.sysconf("SC_CLK_TCK")


def _steal_iowait() -> tuple:
    with open("/proc/stat") as f:
        cpu = f.readline().split()[1:]
    return int(cpu[4]) / CLK, int(cpu[7]) / CLK  # iowait, steal


def _thread_cpu() -> dict:
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                raw = f.read()
        except OSError:
            continue
        comm = raw[raw.index("(") + 1 : raw.rindex(")")]
        fields = raw[raw.rindex(")") + 2 :].split()
        cpu = (int(fields[11]) + int(fields[12])) / CLK
        name = names.get(int(tid), comm)
        if name.startswith("Thread-") or name.startswith("bench-"):
            name = name.split(" ")[0]
        out[name] = out.get(name, 0.0) + cpu
    return out


def _frames(me) -> dict:
    """The three innermost frames of every Python thread but ``me``."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return {
        names.get(tid, str(tid)): " < ".join(
            f"{fs.name}:{os.path.basename(fs.filename)}:{fs.lineno}"
            for fs in traceback.extract_stack(fr)[-3:][::-1]
        )
        for tid, fr in sys._current_frames().items() if tid != me
    }


class Watcher(threading.Thread):
    def __init__(self):
        super().__init__(name="bench-watcher", daemon=True)
        self.stalls, self.samples, self.stacks = [], [], []
        self.stop = threading.Event()

    def run(self):
        me = threading.get_ident()
        last = time.perf_counter()
        next_sample = next_stack = last
        while not self.stop.is_set():
            time.sleep(TICK_S)
            now = time.perf_counter()
            late = now - last - TICK_S
            if late > LATE_S:
                self.stalls.append((last, late, _frames(me)))
            if now >= next_stack:
                self.stacks.append((now, _frames(me)))
                next_stack = now + STACK_S
            if now >= next_sample:
                self.samples.append((now, _steal_iowait(), _thread_cpu()))
                next_sample = now + SAMPLE_S
                now = time.perf_counter()  # the sampling is no stall
            last = now


def _window(out_sent, seconds):
    t0 = min(s.due for s in out_sent)
    return t0, t0 + seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    sent_box = []
    real_load = run.loadgen.Load

    class KeptLoad(real_load):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            sent_box.append(self.sent)

    run.loadgen.Load = KeptLoad
    w = Watcher()
    w.start()
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    out = run.run(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "0"])
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    w.stop.set()
    w.join()

    sent = sent_box[-1]
    t0, t_end = _window(sent, args.seconds)
    n_bins = int(np.ceil(args.seconds))
    frames = np.zeros(n_bins)
    failed = np.zeros(n_bins)
    lat = [[] for _ in range(n_bins)]
    for s in sent:
        b = int((s.due - t0) // 1.0)
        if not 0 <= b < n_bins:
            continue
        if s.ok and s.seen is not None:
            lat[b].append((s.seen - s.due) * 1e3)
            if s.seen < t_end:
                frames[int((s.seen - t0) // 1.0)] += s.n
        else:
            failed[b] += 1
    p95 = [round(float(np.percentile(x, 95)), 2) if x else None for x in lat]

    inside = [x for x in w.samples if t0 <= x[0] <= t_end]
    steal_per_s, iowait_per_s, cpu = [], [], {}
    if len(inside) >= 2:
        for a, b in zip(inside, inside[1:]):
            dt = b[0] - a[0]
            steal_per_s.append(round((b[1][1] - a[1][1]) / dt, 3))
            iowait_per_s.append(round((b[1][0] - a[1][0]) / dt, 3))
        first, last = inside[0][2], inside[-1][2]
        span = inside[-1][0] - inside[0][0]
        cpu = {k: round((v - first.get(k, 0.0)) / span, 3) for k, v in last.items()}
        cpu = dict(sorted(cpu.items(), key=lambda kv: -kv[1])[:12])
    stalls = sorted(
        [x for x in w.stalls if t0 <= x[0] <= t_end], key=lambda x: -x[1]
    )
    seen = sorted(s.seen for s in sent if s.seen is not None and t0 <= s.seen < t_end)
    gap = {}
    if len(seen) >= 2:
        i = int(np.argmax(np.diff(seen)))
        g0, g1 = seen[i], seen[i + 1]
        during = collections.defaultdict(collections.Counter)
        for at, by_thread in w.stacks:
            if g0 <= at <= g1:
                for name, where in by_thread.items():
                    during[name][where] += 1
        gap = {
            "from_s": round(g0 - t0, 3), "ms": round((g1 - g0) * 1e3, 2),
            "threads": {k: v.most_common(3) for k, v in during.items()},
        }
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": out["correct"], "failed": out["failed"],
        "attempted": out["attempted"],
        "metrics": {k: m["value"] for k, m in out["metrics"].items()},
        "frames_per_s_by_second": frames.tolist(),
        "failed_by_second": failed.tolist(),
        "p95_ms_by_second": p95,
        "stalls_in_window": len(stalls),
        "stalled_s_in_window": round(sum(x[1] for x in stalls), 4),
        "longest_stalls": [
            {"at_s": round(x[0] - t0, 3), "ms": round(x[1] * 1e3, 2), "threads": x[2]}
            for x in stalls[:5]
        ],
        "longest_answer_gap": gap,
        "cpu_s_per_s_by_thread": cpu,
        "steal_cpu_s_per_s": steal_per_s,
        "iowait_cpu_s_per_s": iowait_per_s,
        "ctx_switches": {"voluntary": ru1.ru_nvcsw - ru0.ru_nvcsw,
                         "involuntary": ru1.ru_nivcsw - ru0.ru_nivcsw,
                         "major_faults": ru1.ru_majflt - ru0.ru_majflt},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(registry.ROOT / "src"))
    sys.exit(main())
