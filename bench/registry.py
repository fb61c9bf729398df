"""Find what ``BENCHMARK.json`` names, by name, in files of their own.

- a configuration: the file its ``configs`` entry gives;
- the family that knows a configuration's model: ``families/<family>.py``,
  named by the configuration's ``family``, a module with ``make_params``,
  ``frame_shape``, ``forward``, ``frame_ops``, ``system`` and
  ``kernel_calls`` (see ``families/cnn_chain.py``);
- a traffic mix: ``traffic/<traffic>.json``, data;
- the loop that drives a mix: ``traffic/<loop>.py``, named by the mix's
  ``loop``, a module with ``threads(load, t0, t_end)`` (see ``loadgen``);
- a metric: ``metrics/<name>.py``, a module with ``read(ctx)``, or, where
  no file has the whole name, ``metrics/<stem>.py`` for the part of the
  name before its first dot: ``dispatch_ms.bulk`` and
  ``dispatch_ms.cameras`` share ``metrics/dispatch_ms.py``.

Adding a configuration, a model family, a mix, a loop or a metric is adding
a file and an entry; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bm: dict, name: str) -> dict:
    for c in bm["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path, kind: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + path.stem.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_loop(name: str, bench: pathlib.Path = BENCH):
    """The module ``traffic/<name>.py`` that drives mixes whose ``loop``
    is ``name``."""
    return _module(bench / "traffic" / f"{name}.py", "loop")


def family(name, bench: pathlib.Path = BENCH):
    """The module ``families/<name>.py``, ``name`` being a configuration's
    ``family``. There is no default: a configuration that names none, or
    names no such file, is an error."""
    if not name:
        raise KeyError("the configuration names no family")
    return _module(bench / "families" / f"{name}.py", "family")


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The module ``metrics/<name>.py``, else ``metrics/<stem>.py`` with
    the name's part before its first dot; its ``read(ctx)`` returns the
    value, or None where the run holds nothing to read."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        path = bench / "metrics" / f"{name.split('.', 1)[0]}.py"
    return _module(path, "metric")


def cell_metrics(bm: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    without a ``workloads`` list, and those whose list names the cell."""
    return [
        m for m in bm[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
