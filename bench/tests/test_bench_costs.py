"""Operations and bytes from shapes, and the peaks table."""
import json

import pytest

import costs
import registry

LENET5 = {
    "input_hw": [28, 28],
    "input_channels": 1,
    "conv_layers": [
        {"n_out": 20, "kernel": 5, "padding": "VALID", "pool": 2, "act": "tanh"},
        {"n_out": 50, "kernel": 5, "padding": "VALID", "pool": 2, "act": "tanh"},
    ],
    "fc_dims": [500],
    "n_classes": 10,
}


def _cifar10():
    bm = registry.benchmark()
    return registry.config(bm, "cifar10_int8")["topology"]


def test_cifar10_ops_per_frame():
    topo = _cifar10()
    assert costs.conv_ops(topo) == 24_576_000
    assert costs.head_ops(topo) == 132_352
    assert costs.frame_ops(topo) == 24_708_352


def test_both_cifar10_configs_share_the_topology():
    bm = registry.benchmark()
    a = registry.config(bm, "cifar10_int8")["topology"]
    b = registry.config(bm, "cifar10_fp32")["topology"]
    assert a == b


def test_lenet5_valid_convs_match_the_paper_workload():
    # Paper Table 4: 3.8 Mop for the LeNet5 feature extractor.
    assert costs.conv_shapes(LENET5) == [(1, 20, 5, 24, 24), (20, 50, 5, 8, 8)]
    assert costs.conv_ops(LENET5) == 3_776_000
    assert costs.feature_shape(LENET5) == (4, 4, 50)
    assert costs.head_ops(LENET5) == 2 * (800 * 500 + 500 * 10)


@pytest.mark.parametrize("act_bytes", [1, 4])
def test_conv_bytes(act_bytes):
    topo = _cifar10()
    weights = (75 * 32 + 800 * 32 + 800 * 64) * act_bytes + (32 + 32 + 64) * 4
    per_frame = 32 * 32 * 3 * act_bytes + 4 * 4 * 64 * 4
    assert costs.conv_bytes(topo, 256, act_bytes) == 256 * per_frame + weights


def test_roofline_picks_the_larger_bound():
    int8 = {"peak": "int8_ops_per_s"}
    t, bound = costs.roofline_s(393e12, 1.0, "TPU v5 lite", int8)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = costs.roofline_s(1.0, 819e9, "TPU v5 lite", int8)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("cpu")


def test_peaks_table_names_its_source():
    table = json.loads(costs.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"]["int8_ops_per_s"] == 393e12
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("name", ["lenet5", "cifar10", "svhn", "cifar10_full",
                                  "cifar10_strided"])
def test_shapes_and_ops_agree_with_the_program_topologies(name):
    """The program's own topologies, written out as configuration data,
    price the same in ``costs`` as the program's arithmetic says."""
    from repro.models.cnn import ALL_TOPOLOGIES

    t = ALL_TOPOLOGIES[name]
    topology = {
        "input_hw": list(t.input_shape),
        "input_channels": t.input_channels,
        "conv_layers": [
            {"n_out": c.n_out, "kernel": c.kernel, "padding": c.padding,
             "pool": c.pool, "act": c.act, "stride": c.stride,
             "pool_stride": c.pool_stride}
            for c in t.conv_layers
        ],
        "fc_dims": list(t.fc_dims),
        "n_classes": t.n_classes,
    }
    assert costs.conv_shapes(topology) == [tuple(s) for s in t.conv_shapes()]
    assert costs.feature_shape(topology) == tuple(t.feature_shape())
    assert costs.conv_ops(topology) == t.feature_extractor_ops()
