"""Sweep the offered rate of an open-loop cell, to find its knee.

    python bench/sweep.py --workload cifar10_int8.cameras --seconds 5 \
        --seed 1 --rates 500 1000 2000 4000

Runs the cell once per rate, in one process, with the traffic file's
``rate_per_s`` replaced, and prints one JSON line per rate. The knee is
the highest rate at which no request failed (shed, deadline passed) and
the 95th percentile latency stays under 33 ms, a 30 fps camera's frame
time; the cell's traffic file then offers a fixed fraction of it.
``PERF.md`` records the sweep behind each rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import registry
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for rate in args.rates:
        try:
            out = run.run(
                ["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0"],
                traffic_overrides={"rate_per_s": rate},
            )
        except run.RunFailed as e:
            print(json.dumps({"rate_per_s": rate, "run_failed": str(e)}), flush=True)
            continue
        print(json.dumps({
            "rate_per_s": rate, "attempted": out["attempted"],
            "failed": out["failed"], "correct": out["correct"],
            "metrics": {k: m["value"] for k, m in out["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(registry.ROOT / "src"))
    sys.exit(main())
