"""Everything ``BENCHMARK.json`` names resolves to a file of its own, and a
new configuration, model family, traffic mix or metric is found by name
from files alone."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FAMILY_API = ("make_params", "frame_shape", "forward", "frame_ops", "system",
              "kernel_calls")


@pytest.fixture(scope="module")
def bm():
    return registry.benchmark()


def test_every_named_piece_resolves(bm):
    for c in bm["configs"]:
        cfg = registry.config(bm, c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/configs/")
        family = registry.family(cfg["family"])
        assert all(callable(getattr(family, f)) for f in FAMILY_API)
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


# A model family that exists only as new files: a conv chain whose conv
# layers carry batch norm, which the reference applies as it stands and the
# system folds into the conv before the program compiles it, as a deployment
# folds it. ``cnn_chain`` refuses the ``batch_norm`` key.
BN_FAMILY = '''
# Conv chains with batch norm: SAME convs, each with bias, batch norm, a
# 2x2 max-pool and relu, then dense layers with tanh between them.
import jax
import jax.numpy as jnp

EPS = 1e-3


def frame_shape(topology):
    return (*topology["input_hw"], topology["input_channels"])


def _dims(topology):
    (h, w), c = topology["input_hw"], topology["input_channels"]
    convs = []
    for layer in topology["conv_layers"]:
        convs.append((c, layer["n_out"], layer["kernel"], h, w))
        c, h, w = layer["n_out"], h // 2, w // 2
    return convs, [h * w * c, *topology["fc_dims"], topology["n_classes"]]


def frame_ops(topology):
    convs, dims = _dims(topology)
    return 2 * (sum(c * n * k * k * h * w for c, n, k, h, w in convs)
                + sum(a * b for a, b in zip(dims[:-1], dims[1:])))


def make_params(topology, key_words):
    convs, dims = _dims(topology)

    def init(key):
        keys = iter(jax.random.split(key, 6 * len(convs) + 2 * len(dims)))

        def normal(shape, std):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        conv = [{"w": normal((k, k, c, n), (2.0 / (k * k * c)) ** 0.5),
                 "b": normal((n,), 0.1), "gamma": 1.0 + normal((n,), 0.1),
                 "beta": normal((n,), 0.1), "mean": normal((n,), 0.1),
                 "var": jnp.exp(normal((n,), 0.2))} for c, n, k, _, _ in convs]
        fc = [{"w": normal((a, b), (2.0 / a) ** 0.5), "b": normal((b,), 0.1)}
              for a, b in zip(dims[:-1], dims[1:])]
        return {"conv": conv, "fc": fc}

    key = jax.random.wrap_key_data(jnp.asarray(key_words, jnp.uint32))
    return jax.jit(init)(key)


def forward(params, x, topology, *, fake_quant_bits, dot_operands):
    if fake_quant_bits is not None or dot_operands != "float32":
        raise ValueError("this family is checked in float32 only")
    hp = jax.lax.Precision.HIGHEST
    h = x
    for layer, p in zip(topology["conv_layers"], params["conv"]):
        h = jax.lax.conv_general_dilated(
            h, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=hp) + p["b"]
        if layer["batch_norm"]:
            h = (h - p["mean"]) * p["gamma"] / jnp.sqrt(p["var"] + EPS) + p["beta"]
        h = jax.lax.reduce_window(h, jnp.array(-jnp.inf, h.dtype), jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = jnp.maximum(h, 0)
    h = h.reshape(h.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        h = jnp.dot(h, p["w"], precision=hp) + p["b"]
        if i < len(params["fc"]) - 1:
            h = jnp.tanh(h)
    return h


def system(cfg, params):
    from repro.core.dhm import QuantSpec, compile_dhm
    from repro.models.cnn import CNNTopology, ConvLayerSpec

    t = cfg["topology"]
    conv = []
    for layer, p in zip(t["conv_layers"], params["conv"]):
        w, b = p["w"], p["b"]
        if layer["batch_norm"]:
            scale = p["gamma"] / jnp.sqrt(p["var"] + EPS)
            w, b = w * scale, (b - p["mean"]) * scale + p["beta"]
        conv.append({"w": w, "b": b})
    topo = CNNTopology(
        name=cfg["name"], input_hw=tuple(t["input_hw"]),
        input_channels=t["input_channels"],
        conv_layers=tuple(
            ConvLayerSpec(n_out=layer["n_out"], kernel=layer["kernel"],
                          padding="SAME", pool=2, act="relu")
            for layer in t["conv_layers"]),
        fc_dims=tuple(t["fc_dims"]), n_classes=t["n_classes"],
    )
    return compile_dhm(topo, {"conv": conv, "fc": params["fc"]},
                       quant=QuantSpec(**cfg["quant"]))


def kernel_calls(plan):
    return len(plan.fusion_groups)
'''
BN_CONFIG = {
    "name": "tiny_bn", "family": "bn_chain", "source": "x",
    "topology": {
        "input_hw": [12, 12], "input_channels": 3,
        "conv_layers": [{"n_out": 8, "kernel": 5, "batch_norm": True},
                        {"n_out": 16, "kernel": 3, "batch_norm": True}],
        "fc_dims": [16], "n_classes": 10,
    },
    "quant": {}, "peak": "bf16_flops_per_s", "kernel_act_bytes": 4,
    "frame_grid_bits": 6,
    "reference": {"fake_quant_bits": None, "dot_operands": "float32",
                  "dtype": "float32"},
    "control": {"fake_quant_bits": None, "dot_operands": "float32",
                "dtype": "bfloat16"},
    "limits": {"logit_gap": 2.0**-10, "frames_off_pct": 1.0, "unanswered": 0},
}
# Runs the copy's own harness in a process of its own, twice: traced, with
# the profile left out and the chip's peaks standing in for the CPU's; then
# with the family's forward perturbed. Prints both results and every value
# the family's frame_ops returned.
RUNNER = """
import json, sys, time
sys.path.insert(0, "bench")
import jax
import costs, registry, run

v5e = costs.peaks("TPU v5 lite")
costs.peaks = lambda kind: v5e
run._jax = lambda: jax


def no_profile(jax, out_dir, until, window_mark):
    window_mark()
    time.sleep(max(0.0, until - time.perf_counter()))
    window_mark()


run._profile = no_profile
family = registry.family("bn_chain")
ops, frame_ops, forward = [], family.frame_ops, family.forward
family.frame_ops = lambda topology: ops.append(frame_ops(topology)) or ops[-1]
argv = ["--workload", "tiny_bn.bulk", "--seed", str(2**33 + 21), "--seconds", "0.6"]
small = json.loads(sys.argv[1])
sound = run.run(argv + ["--trace", "1"], chip_checks=False, traffic_overrides=small)
family.forward = lambda *a, **k: 1.01 * forward(*a, **k)
perturbed = run.run(argv + ["--trace", "0"], chip_checks=False, traffic_overrides=small)
print(json.dumps({"sound": sound, "perturbed": perturbed, "frame_ops": ops}))
"""


def _files(root: pathlib.Path) -> dict:
    skip = {".jax_cache", ".traces", ".tpu_logs", "__pycache__"}
    return {
        p.relative_to(root): p.read_bytes() for p in sorted(root.rglob("*"))
        if p.is_file() and not skip & set(p.relative_to(root).parts)
    }


def test_a_later_pr_adds_a_model_by_files_alone(tmp_path, bm):
    """Copy the benchmark, add a model family, a configuration that only it
    reads and a cell as new files and entries, and run the cell end to end
    on the CPU through the copy's own ``run.run``: correct as built, not
    correct with the family's forward perturbed, and ``step_mfu_pct``
    priced by the family's own ``frame_ops``. No file of the copy that was
    there before changes."""
    root = tmp_path / "checkout"
    before = _files(registry.BENCH)
    shutil.copytree(registry.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    (root / "bench" / "families" / "bn_chain.py").write_text(BN_FAMILY)
    (root / "bench" / "configs" / "tiny_bn.json").write_text(json.dumps(BN_CONFIG))
    new = json.loads(json.dumps(bm))
    new["configs"].append({"name": "tiny_bn", "source": "x",
                           "file": "bench/configs/tiny_bn.json", "reduced": [],
                           "why": "x"})
    new["workloads"].append({"name": "tiny_bn.bulk", "config": "tiny_bn",
                             "traffic": "bulk", "chips": 1, "why": "x"})
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in ("frames_per_s", "step_mfu_pct"):
            m["workloads"].append("tiny_bn.bulk")
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    with pytest.raises(ValueError, match="batch_norm"):
        registry.family("cnn_chain").frame_ops(BN_CONFIG["topology"])

    small = {"clients": 2, "frames_per_request": 8, "pool_frames": 64,
             "warmup_s": 0.2, "engine": {"microbatch": 16}}
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(registry.ROOT / "src")}
    p = subprocess.run(
        [sys.executable, "-c", RUNNER, json.dumps(small)], cwd=root, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sound, perturbed = out["sound"], out["perturbed"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    family = registry.family("bn_chain", root / "bench")
    assert out["frame_ops"] == [family.frame_ops(BN_CONFIG["topology"])]
    assert sound["metrics"]["step_mfu_pct"]["value"] > 0
    assert not perturbed["correct"], perturbed["checks"]

    after = _files(root / "bench")
    assert {k: after.get(k) for k in before} == before
    assert set(after) - set(before) == {pathlib.Path("families/bn_chain.py"),
                                        pathlib.Path("configs/tiny_bn.json")}


def test_unknown_names_are_errors(bm):
    with pytest.raises(KeyError):
        registry.cell(bm, "no_such.cell")
    with pytest.raises(KeyError):
        registry.config(bm, "no_such_config")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError, match="no family"):
        registry.family("no_such_family")
    with pytest.raises(KeyError, match="names no family"):
        registry.family(None)


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
