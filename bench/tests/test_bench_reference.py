"""The benchmark's plain reference agrees with the program's own oracle,
``cnn_apply_reference``, at a small size, and the control (one precision
step below the configuration) fails the configuration's limits."""
import json

import numpy as np
import pytest

import check
import costs
import inputs
import reference
import registry

SMALL = {
    "input_hw": [12, 12],
    "input_channels": 3,
    "conv_layers": [
        {"n_out": 8, "kernel": 5, "padding": "SAME", "pool": 2, "act": "tanh"},
        {"n_out": 16, "kernel": 3, "padding": "SAME", "pool": 2, "act": "tanh"},
    ],
    "fc_dims": [16],
    "n_classes": 10,
}


def _program_reference(params, frames, bits):
    import jax
    from repro.models.cnn import CNNTopology, ConvLayerSpec, cnn_apply_reference

    topo = CNNTopology(
        name="small", input_hw=(12, 12), input_channels=3,
        conv_layers=tuple(ConvLayerSpec(**c) for c in SMALL["conv_layers"]),
        fc_dims=(16,), n_classes=10,
    )
    with jax.default_matmul_precision("highest"):
        return np.asarray(cnn_apply_reference(
            params, topo, frames, weight_bits=bits, act_bits=bits
        ))


@pytest.mark.parametrize("bits", [None, 6])
def test_matches_the_program_oracle(bits):
    rs = inputs.streams(2**33 + 7)
    params = inputs.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(SMALL, 6, 24, rs["frames"])
    got = reference.logits(params, frames, SMALL, fake_quant_bits=bits)
    want = _program_reference(params, frames, bits)
    scale = np.abs(want).max()
    if bits is None:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    else:
        # On the 6-bit grids every product is exact, so the two agree but
        # for float32 summation order.
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * scale)


def test_bfloat16_operands_round_each_product_input():
    rs = inputs.streams(11)
    params = inputs.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(SMALL, 6, 8, rs["frames"])
    exact = reference.logits(params, frames, SMALL)
    rounded = reference.logits(params, frames, SMALL, dot_operands="bfloat16")
    gap = np.abs(exact - rounded).max() / np.abs(exact).max()
    assert 1e-5 < gap < 2.0**-5


def test_blocks_cover_every_frame():
    rs = inputs.streams(3)
    params = inputs.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(SMALL, 6, reference.BLOCK + 3, rs["frames"])
    whole = reference.logits(params, frames, SMALL)
    assert whole.shape == (reference.BLOCK + 3, 10)
    np.testing.assert_array_equal(
        whole[-3:], reference.logits(params, frames[-3:], SMALL)
    )


@pytest.mark.parametrize("config", ["cifar10_int8", "cifar10_fp32"])
def test_control_fails_the_limits(config):
    """The control: the reference one precision step below the
    configuration (int4 for the 6-bit integer plan, bfloat16 for float32),
    in the program's place, at the configuration's own widths."""
    bm = registry.benchmark()
    cfg = registry.config(bm, config)
    rs = inputs.streams(2**31 + 99)
    params = inputs.make_params(cfg["topology"], rs["weights"])
    frames = inputs.frame_pool(cfg["topology"], cfg["frame_grid_bits"], 32, rs["frames"])
    ref = reference.logits(params, frames, cfg["topology"], **cfg["reference"])
    ctl = reference.logits(params, frames, cfg["topology"], **cfg["control"])
    nums = {**check.numbers(ctl, ref), "unanswered": 0}
    correct, checks = check.verdict(nums, cfg["limits"])
    assert not correct, checks


def test_inputs_repeat_from_the_seed():
    a, b = inputs.streams(2**40 + 1), inputs.streams(2**40 + 1)
    np.testing.assert_array_equal(a["weights"], b["weights"])
    fa = inputs.frame_pool(SMALL, 6, 4, a["frames"])
    fb = inputs.frame_pool(SMALL, 6, 4, b["frames"])
    np.testing.assert_array_equal(fa, fb)
    assert np.all(np.abs(fa * 16 - np.round(fa * 16)) == 0)
    assert fa.min() >= -2.0 and fa.max() <= 1.9375


# Caffe's cifar10_full (overlapping 3x3/stride-2 pools) and a strided
# variant, cut small: the layer keys the cifar10 configurations leave at
# their defaults.
OVERLAP = {
    "input_hw": [16, 12],
    "input_channels": 3,
    "conv_layers": [
        {"n_out": 8, "kernel": 5, "padding": "SAME", "pool": 3,
         "pool_stride": 2, "act": "relu"},
        {"n_out": 16, "kernel": 3, "padding": "VALID", "pool": 3,
         "pool_stride": 2, "act": "tanh"},
    ],
    "fc_dims": [16],
    "n_classes": 10,
}
STRIDED = {
    "input_hw": [15, 15],
    "input_channels": 3,
    "conv_layers": [
        {"n_out": 8, "kernel": 5, "padding": "SAME", "stride": 2, "pool": 0,
         "act": "relu"},
        {"n_out": 16, "kernel": 3, "padding": "VALID", "stride": 2, "pool": 0,
         "act": "none"},
        {"n_out": 16, "kernel": 3, "padding": "SAME", "pool": 2, "act": "relu"},
    ],
    "fc_dims": [16],
    "n_classes": 10,
}


def _program_topology(topology):
    from repro.models.cnn import CNNTopology, ConvLayerSpec

    return CNNTopology(
        name="t", input_hw=tuple(topology["input_hw"]),
        input_channels=topology["input_channels"],
        conv_layers=tuple(ConvLayerSpec(**c) for c in topology["conv_layers"]),
        fc_dims=tuple(topology["fc_dims"]), n_classes=topology["n_classes"],
    )


@pytest.mark.parametrize("bits", [None, 6])
@pytest.mark.parametrize("name", ["overlap", "strided"])
def test_matches_the_program_oracle_on_strides_and_activations(name, bits):
    import jax
    from repro.models.cnn import cnn_apply_reference

    topology = {"overlap": OVERLAP, "strided": STRIDED}[name]
    rs = inputs.streams(2**35 + 3)
    params = inputs.make_params(topology, rs["weights"])
    frames = inputs.frame_pool(topology, 6, 16, rs["frames"])
    got = reference.logits(params, frames, topology, fake_quant_bits=bits)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(cnn_apply_reference(
            params, _program_topology(topology), frames,
            weight_bits=bits, act_bits=bits,
        ))
    atol = (1e-5 if bits is None else 1e-6) * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("layer_change", [
    {"dilation": 2}, {"act": "gelu"}, {"padding": "CAUSAL"}, {"stride": 0},
])
def test_a_layer_the_reference_does_not_implement_is_refused(layer_change):
    topology = json.loads(json.dumps(SMALL))
    topology["conv_layers"][0].update(layer_change)
    rs = inputs.streams(5)
    params = inputs.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(SMALL, 6, 8, rs["frames"])
    with pytest.raises(ValueError, match="conv layer 0"):
        reference.logits(params, frames, topology)


def test_a_layer_without_its_activation_is_refused():
    topology = json.loads(json.dumps(SMALL))
    del topology["conv_layers"][1]["act"]
    with pytest.raises(ValueError, match="missing"):
        costs.conv_ops(topology)
