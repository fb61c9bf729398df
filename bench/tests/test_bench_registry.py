"""Everything ``BENCHMARK.json`` names resolves to a file of its own, and a
new configuration, traffic mix or metric is found by name from files
alone."""
import json
import re
import shutil

import pytest

import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return registry.benchmark()


def test_every_named_piece_resolves(bm):
    for c in bm["configs"]:
        cfg = registry.config(bm, c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
    for w in bm["workloads"]:
        registry.config(bm, w["config"])
        t = registry.traffic(w["traffic"])
        assert callable(registry.traffic_loop(t["loop"]).threads)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)


def test_contract_shape(bm):
    assert set(bm) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bm["command"] == ["python3", "bench/run.py"] and bm["paths"] == ["bench"]
    cells = {w["name"] for w in bm["workloads"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        # every cell a per-layer metric names reports the metric it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in bm["workloads"]:
        assert w["chips"] == 1 and NAME.match(w["name"]) and len(w["why"]) <= 200
        reported = registry.cell_metrics(bm, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert registry.cell_metrics(bm, w["name"], "per_layer")


def test_a_later_pr_adds_pieces_by_files_alone(tmp_path, bm):
    """Copy the benchmark, add a configuration, a traffic mix and a metric
    as new files and entries, and find each by name."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    cfg = registry.config(bm, "cifar10_int8")
    cfg["name"] = "svhn_int8"
    (root / "bench" / "configs" / "svhn_int8.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "cameras_burst.json").write_text(json.dumps(
        {"loop": "burst", "rate_per_s": 30, "burst": 64,
         "frames_per_request": 1, "pool_frames": 4096, "warmup_s": 1.0,
         "engine": {"microbatch": 64}}
    ))
    (root / "bench" / "traffic" / "burst.py").write_text(
        "def threads(load, t0, t_end):\n    return ['bursts']\n"
    )
    (root / "bench" / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n"
    )
    new = json.loads(json.dumps(bm))
    new["configs"].append({"name": "svhn_int8", "source": "https://arxiv.org/abs/1712.04322",
                           "file": "bench/configs/svhn_int8.json", "reduced": [], "why": "x"})
    new["workloads"].append({"name": "svhn_int8.cameras_burst", "config": "svhn_int8",
                             "traffic": "cameras_burst", "chips": 1, "why": "x"})
    new["per_layer"].append({"name": "queue_wait_ms", "unit": "ms", "better": "lower",
                             "source": "program_counter", "layer": "x",
                             "moves": "latency_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    got = registry.benchmark(root)
    cell = registry.cell(got, "svhn_int8.cameras_burst")
    assert registry.config(got, cell["config"], root)["name"] == "svhn_int8"
    mix = registry.traffic(cell["traffic"], root / "bench")
    assert mix["burst"] == 64
    assert registry.traffic_loop(mix["loop"], root / "bench").threads(
        None, 0.0, 1.0) == ["bursts"]
    assert registry.metric_reader("queue_wait_ms", root / "bench").read(None) == 1.5
    names = {m["name"] for m in registry.cell_metrics(got, cell["name"], "per_layer")}
    assert "queue_wait_ms" in names


def test_unknown_names_are_errors(bm):
    with pytest.raises(KeyError):
        registry.cell(bm, "no_such.cell")
    with pytest.raises(KeyError):
        registry.config(bm, "no_such_config")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no_such_metric")


def test_a_metric_name_falls_back_to_its_stem(tmp_path):
    """``dispatch_ms.bulk`` and ``dispatch_ms.cameras`` read the one
    ``metrics/dispatch_ms.py``; a file with the whole name comes first."""
    a = registry.metric_reader("dispatch_ms.bulk")
    assert a is registry.metric_reader("dispatch_ms.cameras")
    assert a.__file__.endswith("metrics/dispatch_ms.py")
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x_ms.py").write_text("def read(ctx):\n    return 1\n")
    (tmp_path / "metrics" / "x_ms.b.py").write_text("def read(ctx):\n    return 2\n")
    assert registry.metric_reader("x_ms.a", tmp_path).read(None) == 1
    assert registry.metric_reader("x_ms.b", tmp_path).read(None) == 2
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("y_ms.a", tmp_path)


def test_an_unknown_loop_is_an_error():
    with pytest.raises(FileNotFoundError, match="no loop"):
        registry.traffic_loop("no_such_loop")
