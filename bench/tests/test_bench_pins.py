"""The chain family reproduces, bit for bit, the yardstick the benchmark
read before the harness reached models through families: the weights from
a seed, the frame pool, the reference and control logits, the operations
per frame and the kernel's bytes.

``cnn_chain_golden.json`` holds those values as the harness computed them
before the move (``inputs.make_params``, ``inputs.frame_pool``,
``reference.logits`` and ``costs``), on the CPU, for both cifar10
configurations and the five program topologies, at seed ``2**33 + 15``
over 16 pool frames. Arrays are held as the sha256 of their bytes. The
float32 logits depend on the CPU backend's summation order: they were
recorded on XLA's multi-threaded CPU backend, with two cores or more.
"""
import hashlib
import json
import pathlib

import numpy as np
import pytest

import inputs
import reference
import registry

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "cnn_chain_golden.json").read_text()
)
CASES = sorted(GOLDEN["cases"])
chain = registry.family("cnn_chain")


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def _made(case: dict):
    rs = inputs.streams(GOLDEN["seed"])
    params = chain.make_params(case["topology"], rs["weights"])
    pool = inputs.frame_pool(
        chain.frame_shape(case["topology"]), case["grid_bits"], GOLDEN["frames"],
        rs["frames"],
    )
    return params, pool


def test_the_configurations_still_state_the_pinned_model():
    bm = registry.benchmark()
    for name in ("cifar10_int8", "cifar10_fp32"):
        cfg, case = registry.config(bm, name), GOLDEN["cases"][name]
        assert cfg["family"] == "cnn_chain"
        assert cfg["topology"] == case["topology"]
        assert cfg["frame_grid_bits"] == case["grid_bits"]
        assert cfg["kernel_act_bytes"] == case["act_bytes"]
        assert {"reference": cfg["reference"], "control": cfg["control"]} == case[
            "settings"]


@pytest.mark.parametrize("name", CASES)
def test_param_bits_and_frame_pool(name):
    import jax

    case = GOLDEN["cases"][name]
    params, pool = _made(case)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {jax.tree_util.keystr(p): _sha(v) for p, v in leaves} == case["params"]
    assert _sha(pool) == case["pool"]


@pytest.mark.parametrize("name", CASES)
def test_reference_and_control_logits(name):
    case = GOLDEN["cases"][name]
    params, pool = _made(case)
    got = {
        k: _sha(reference.logits(params, pool, chain.forward, case["topology"], **s))
        for k, s in case["settings"].items()
    }
    assert got == case["logits"]


@pytest.mark.parametrize("name", CASES)
def test_ops_and_bytes(name):
    case = GOLDEN["cases"][name]
    topo = case["topology"]
    assert chain.frame_ops(topo) == case["frame_ops"]
    assert chain.conv_ops(topo) == case["conv_ops"]
    assert {
        n: chain.conv_bytes(topo, int(n), case["act_bytes"]) for n in case["conv_bytes"]
    } == case["conv_bytes"]
