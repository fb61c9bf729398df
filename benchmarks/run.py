"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes the kernel rows to
``BENCH_kernels.json`` (machine-readable, one file per run: schema
``{"benchmark", "jax_backend", "rows": [{name, us_per_call, derived, and
per-row extras such as path/speedup_vs_seed}]}``) so the perf trajectory
of the Pallas kernels is recorded across PRs. ``BENCH_kernels.json`` is a
snapshot (overwritten per run); every run additionally APPENDS its record
to ``BENCH_history.jsonl`` — one JSON line per run with the git SHA and a
UTC timestamp — so the cross-PR perf trajectory survives instead of being
clobbered. Invoke as ``PYTHONPATH=src python -m benchmarks.run`` (add
``--full`` to run the slow full Fig. 3 sweep for all three CNNs and the
full roofline dump).
"""
from __future__ import annotations

import argparse
import csv
import datetime
import json
import subprocess
import sys


def _git_sha() -> str:
    """Current commit SHA (with a -dirty suffix for uncommitted changes);
    'unknown' outside a git checkout. The benchmark artifacts themselves
    (BENCH*) are excluded from the dirty check — the run rewrites them
    before this stamp, and a record must not call a clean code state
    dirty just because it recorded itself."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, check=True, timeout=10,
        ).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--", ".",
             ":(exclude)BENCH_kernels.json", ":(exclude)BENCH.csv",
             ":(exclude)BENCH_history.jsonl"],
            capture_output=True, text=True, check=True, timeout=10,
        ).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:  # noqa: BLE001 — not a git checkout / no git binary
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full Fig. 3 sweep (all 3 CNNs)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        e2e_bench,
        fig3_bitwidth,
        kernel_bench,
        serve_bench,
        table1_param_classes,
        table2_mult_strategies,
        table3_device_fit,
        table4_throughput,
    )

    rows = []
    rows += table1_param_classes.run()
    rows += table2_mult_strategies.run()
    rows += table3_device_fit.run()
    rows += table4_throughput.run()
    rows += fig3_bitwidth.run(
        networks=("lenet5", "cifar10", "svhn") if args.full else ("lenet5",)
    )
    kernel_rows = kernel_bench.run()
    # End-to-end compiled-plan rows (frames/sec per topology, fp32 vs
    # quantized plan) ride in the same record: the Table-4-style
    # throughput trajectory per PR.
    kernel_rows += e2e_bench.run()
    # Serving-under-load rows (path: serve_load): p50/p99 latency and
    # shed/error rates vs offered load through the fault-tolerant Engine.
    kernel_rows += serve_bench.run()
    rows += kernel_rows

    # Machine-readable kernel perf record (seed path vs fused path, plus
    # the end-to-end compiled plans).
    import jax

    record = {
        "benchmark": "kernels",
        "jax_backend": jax.default_backend(),
        "rows": kernel_rows,
    }
    with open("BENCH_kernels.json", "w") as f:
        json.dump(record, f, indent=2)
    print("# wrote BENCH_kernels.json", file=sys.stderr)

    # Append this run to the cross-PR trajectory (BENCH_kernels.json is a
    # snapshot; the history is what plots perf over time).
    with open("BENCH_history.jsonl", "a") as f:
        f.write(
            json.dumps(
                {
                    "git_sha": _git_sha(),
                    "timestamp": datetime.datetime.now(
                        datetime.timezone.utc
                    ).isoformat(timespec="seconds"),
                    **record,
                }
            )
            + "\n"
        )
    print("# appended BENCH_history.jsonl", file=sys.stderr)

    # Roofline summary rows (from the dry-run artifacts, if present).
    try:
        from benchmarks import roofline

        for mesh in ("16x16", "2x16x16"):
            for r in roofline.table(mesh):
                if r.get("compute_s") is None:
                    continue
                rows.append(
                    {
                        "name": f"roofline/{r['arch']}/{r['shape']}/{mesh}",
                        "us_per_call": max(
                            r["compute_s"], r["memory_s"], r["collective_s"]
                        )
                        * 1e6,
                        "derived": (
                            f"dominant={r['dominant']} "
                            f"frac={r['roofline_fraction']:.3f} "
                            f"useful={r['useful_ratio']:.2f}"
                        ),
                    }
                )
    except Exception as e:  # noqa: BLE001 — roofline needs dry-run artifacts
        print(f"# roofline skipped: {e}", file=sys.stderr)

    w = csv.writer(sys.stdout)
    w.writerow(["name", "us_per_call", "derived"])
    for r in rows:
        w.writerow([r["name"], f"{r['us_per_call']:.1f}", r["derived"]])


if __name__ == "__main__":
    main()
