"""The straight conv chain: the paper's networks (arXiv:1712.04322, Table 1).

A configuration of this family gives its ``topology`` as

- ``input_hw`` ``[H, W]`` and ``input_channels`` C of the frame;
- ``conv_layers``: each conv (``n_out`` filters, ``kernel`` K, ``stride``,
  ``padding`` SAME or VALID), bias, max-pool (``pool`` window, 0 for none,
  ``pool_stride``), then ``act`` (tanh, relu or none);
- ``fc_dims``: the hidden dense widths, each followed by tanh, and
  ``n_classes``, the last dense layer's width.

``layers`` refuses a layer key or value the functions here do not
implement, so that a configuration is never priced or checked as some
other network. Everything here but ``system`` and ``kernel_calls`` is the
yardstick and imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import _grid, _pow2_fake_quant

LAYER_KEYS = {"n_out", "kernel", "padding", "pool", "act", "stride", "pool_stride"}
REQUIRED = ("n_out", "kernel", "padding", "pool", "act")
PADDINGS = ("SAME", "VALID")
ACT = {"tanh": jnp.tanh, "relu": lambda v: jnp.maximum(v, 0), "none": lambda v: v}


def layers(topology: dict) -> list:
    """The conv layers as the yardstick reads them, each with its
    ``stride`` (1 where not given) and ``pool_stride`` (the pool window
    where not given). A key not implemented here, a missing key or an
    unknown value is an error."""
    out = []
    for i, layer in enumerate(topology["conv_layers"]):
        unknown = set(layer) - LAYER_KEYS
        missing = [k for k in REQUIRED if k not in layer]
        if unknown or missing:
            raise ValueError(
                f"conv layer {i}: keys {sorted(unknown)} not implemented, "
                f"{missing} missing; the yardstick reads {sorted(LAYER_KEYS)}"
            )
        full = {"stride": 1, **layer}
        if full.get("pool_stride") is None:
            full["pool_stride"] = full["pool"]
        if full["padding"] not in PADDINGS or full["act"] not in ACT:
            raise ValueError(
                f"conv layer {i}: padding {full['padding']!r} or act "
                f"{full['act']!r} not one of {PADDINGS} and {tuple(ACT)}"
            )
        if full["stride"] < 1 or (full["pool"] and full["pool_stride"] < 1):
            raise ValueError(f"conv layer {i}: strides must be at least 1")
        out.append(full)
    return out


def frame_shape(topology: dict) -> tuple:
    """(H, W, C) of one input frame."""
    return (*topology["input_hw"], topology["input_channels"])


def _conv_hw(h: int, w: int, layer: dict) -> tuple:
    """(H, W) after the conv alone: SAME gives ``ceil(H / stride)``,
    VALID ``(H - kernel) // stride + 1``."""
    s = layer["stride"]
    if layer["padding"] == "SAME":
        return -(-h // s), -(-w // s)
    k = layer["kernel"]
    return (h - k) // s + 1, (w - k) // s + 1


def _pool_hw(h: int, w: int, layer: dict) -> tuple:
    """(H, W) after the ``pool x pool`` max-pool of stride ``pool_stride``
    (no padding); unchanged where ``pool`` is 0."""
    p, s = layer["pool"], layer["pool_stride"]
    if not p:
        return h, w
    return (h - p) // s + 1, (w - p) // s + 1


def conv_shapes(topology: dict) -> list:
    """Per conv layer ``(C_in, N_out, K, H_conv, W_conv)``, the conv's
    output size taken before the max-pool that follows it."""
    h, w = topology["input_hw"]
    c = topology["input_channels"]
    out = []
    for layer in layers(topology):
        hc, wc = _conv_hw(h, w, layer)
        out.append((c, layer["n_out"], layer["kernel"], hc, wc))
        h, w = _pool_hw(hc, wc, layer)
        c = layer["n_out"]
    return out


def feature_shape(topology: dict) -> tuple:
    """(H, W, C) of the conv stack's output, the FC head's input."""
    c, n, k, hc, wc = conv_shapes(topology)[-1]
    return (*_pool_hw(hc, wc, layers(topology)[-1]), n)


def _dense_dims(topology: dict) -> list:
    h, w, c = feature_shape(topology)
    return [h * w * c, *topology["fc_dims"], topology["n_classes"]]


def conv_ops(topology: dict) -> int:
    """Operations of the conv stack for one frame: 2 per multiply-add,
    ``2 * sum(C_in * N_out * K * K * H_conv * W_conv)``. Bias, pool and
    activation are not counted."""
    return 2 * sum(c * n * k * k * h * w for c, n, k, h, w in conv_shapes(topology))


def head_ops(topology: dict) -> int:
    """Operations of the FC head for one frame: ``2 * sum(d_in * d_out)``
    over the dense layers, from the flattened features through the hidden
    widths to the classes."""
    dims = _dense_dims(topology)
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def frame_ops(topology: dict) -> int:
    """Operations the whole step spends on one frame: conv stack plus
    head (cifar10: 24,576,000 + 132,352 = 24,708,352)."""
    return conv_ops(topology) + head_ops(topology)


def conv_bytes(topology: dict, n_frames: int, act_bytes: int) -> int:
    """Bytes the fused conv stack must move for ``n_frames`` frames when
    every intermediate stays on chip: each frame read once
    (``H * W * C_in * act_bytes``), the features written once as float32
    (``H' * W' * C' * 4``), and the weights and biases read once per call
    (``(K * K * C_in + 1) * N_out`` elements, ``act_bytes`` for weights
    and 4 for biases)."""
    h, w, c = frame_shape(topology)
    fh, fw, fc = feature_shape(topology)
    weights = sum(
        k * k * ci * n * act_bytes + n * 4
        for ci, n, k, _, _ in conv_shapes(topology)
    )
    return n_frames * (h * w * c * act_bytes + fh * fw * fc * 4) + weights


def param_shapes(topology: dict) -> dict:
    """Leaf shapes in the program's layout: conv kernels HWIO (K, K, C, N),
    dense weights (in, out)."""
    conv, c = [], topology["input_channels"]
    for layer in topology["conv_layers"]:
        k, n = layer["kernel"], layer["n_out"]
        conv.append({"w": (k, k, c, n), "b": (n,)})
        c = n
    dims = _dense_dims(topology)
    fc = [{"w": (a, b), "b": (b,)} for a, b in zip(dims[:-1], dims[1:])]
    return {"conv": conv, "fc": fc}


def make_params(topology: dict, key_words):
    """Random weights on the device, in one jitted call, from the weights
    stream (``inputs.streams``): He-normal kernels (std ``sqrt(2 /
    fan_in)``) and biases of std 0.1, so that the bias path carries
    values."""
    shapes = param_shapes(topology)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple)
    )

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shp in zip(keys, leaves):
            if len(shp) == 1:
                out.append(0.1 * jax.random.normal(k, shp, jnp.float32))
            else:
                fan_in = int(np.prod(shp[:-1]))
                std = np.sqrt(2.0 / fan_in).astype(np.float32)
                out.append(std * jax.random.normal(k, shp, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.wrap_key_data(jnp.asarray(key_words, jnp.uint32))
    return jax.jit(init)(key)


def forward(params, x, topology: dict, *, fake_quant_bits, dot_operands):
    """The chain's forward pass in plain ``jax.numpy`` (see
    ``reference.py`` for the quantization and the operand rounding):
    each conv layer is conv, bias, max-pool, then ``act``; the hidden
    dense layers take tanh."""
    hp = jax.lax.Precision.HIGHEST

    def rd(v):
        if dot_operands == "bfloat16":
            return v.astype(jnp.bfloat16).astype(v.dtype)
        return v

    if fake_quant_bits is not None:
        params = jax.tree_util.tree_map(
            lambda p: _pow2_fake_quant(p, fake_quant_bits), params
        )
        x = _grid(x, fake_quant_bits)
    h = x
    for layer, p in zip(layers(topology), params["conv"]):
        s = layer["stride"]
        h = jax.lax.conv_general_dilated(
            rd(h), rd(p["w"]), window_strides=(s, s),
            padding=layer["padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hp,
        )
        h = h + p["b"]
        k, ks = layer["pool"], layer["pool_stride"]
        if k:
            h = jax.lax.reduce_window(
                h, jnp.array(-jnp.inf, h.dtype), jax.lax.max,
                (1, k, k, 1), (1, ks, ks, 1), "VALID",
            )
        h = ACT[layer["act"]](h)
        if fake_quant_bits is not None:
            h = _grid(h, fake_quant_bits)
    h = h.reshape(h.shape[0], -1)
    n_fc = len(params["fc"])
    for i, p in enumerate(params["fc"]):
        h = jnp.dot(rd(h), rd(p["w"]), precision=hp) + p["b"]
        if i < n_fc - 1:
            h = jnp.tanh(h)
            if fake_quant_bits is not None:
                h = _grid(h, fake_quant_bits)
    return h


def system(cfg: dict, params):
    """The plan ``Engine`` serves: the configuration compiled by the
    program's ``compile_dhm``."""
    from repro.core.dhm import QuantSpec, compile_dhm
    from repro.models.cnn import CNNTopology, ConvLayerSpec

    t = cfg["topology"]
    topo = CNNTopology(
        name=cfg["name"],
        input_hw=tuple(t["input_hw"]),
        input_channels=t["input_channels"],
        conv_layers=tuple(ConvLayerSpec(**layer) for layer in t["conv_layers"]),
        fc_dims=tuple(t["fc_dims"]),
        n_classes=t["n_classes"],
    )
    return compile_dhm(topo, params, quant=QuantSpec(**cfg["quant"]))


def kernel_calls(plan) -> int:
    """Mosaic kernels (``tpu_custom_call``) the compiled forward must
    hold: one per fusion group."""
    return len(plan.fusion_groups)
