"""Readings behind the limits of ``check.py``, taken on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: one run of the cell as ``run.py`` makes it
(the program's numbers, from what the timed path served), then the
control, the reference computed one precision step below what the
configuration states (its file's ``control``), put in the program's place
on the same pool frames and compared with the reference in the same way.
Prints one JSON line per seed and, last, the largest program reading and
the smallest control reading of each number. ``PERF.md`` records the
readings and the limits set between them.
"""
from __future__ import annotations

import argparse
import json
import sys

import check
import inputs
import reference
import registry
import run


def control_numbers(cfg: dict, traffic: dict, seed: int) -> dict:
    family, topology = registry.family(cfg.get("family")), cfg["topology"]
    rs = inputs.streams(seed)
    params = family.make_params(topology, rs["weights"])
    pool = inputs.frame_pool(
        family.frame_shape(topology), cfg["frame_grid_bits"], traffic["pool_frames"],
        rs["frames"],
    )
    ref = reference.logits(params, pool, family.forward, topology, **cfg["reference"])
    ctl = reference.logits(params, pool, family.forward, topology, **cfg["control"])
    return check.numbers(ctl, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bm = registry.benchmark()
    cell = registry.cell(bm, args.workload)
    cfg = registry.config(bm, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    prog, ctl = {}, {}
    for seed in args.seeds:
        out = run.run([
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", str(args.seconds), "--trace", "0",
        ])
        p = {k: c["value"] for k, c in out["checks"].items()}
        c = control_numbers(cfg, traffic, seed)
        for k, v in p.items():
            prog[k] = max(prog.get(k, v), v)
        for k, v in c.items():
            ctl[k] = min(ctl.get(k, v), v)
        print(json.dumps({"seed": seed, "program": p, "control": c,
                          "attempted": out["attempted"], "failed": out["failed"],
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "program_max": prog,
                      "control_min": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(registry.ROOT / "src"))
    sys.exit(main())
