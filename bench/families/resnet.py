"""Residual networks: ResNet (He et al., arXiv:1512.03385, Table 1).

A configuration of this family gives its ``topology`` as

- ``input_hw`` ``[H, W]`` and ``input_channels`` C of the frame;
- ``stem``: a ``kernel`` x ``kernel`` conv of ``n_out`` filters at
  ``stride``, batch norm and ReLU, then a ``pool`` x ``pool`` max-pool of
  stride ``pool_stride`` padded by ``pool_pad`` (-inf) on every side;
- ``widths``: one stage per width, each of ``blocks_per_stage`` basic
  blocks (3x3 conv, batch norm, ReLU, 3x3 conv, batch norm, the shortcut
  added, ReLU). Every stage after the first opens with a stride-2 conv,
  and a block whose shape changes takes a 1x1 projection shortcut with
  its own batch norm (option B); the others add their input as it is;
- ``n_classes``: global average pooling, then one dense layer.

Every conv is SAME-padded as XLA pads (``(ceil(H / s) - 1) * s + K - H``
rows in all, the extra one at the bottom). Batch norm is eval-mode with
epsilon 1e-5. Everything here but ``system`` and ``kernel_calls`` is the
yardstick and imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import _grid, _pow2_fake_quant

TOPOLOGY_KEYS = {"input_hw", "input_channels", "stem", "widths",
                 "blocks_per_stage", "n_classes"}
STEM_KEYS = {"n_out", "kernel", "stride", "pool", "pool_stride", "pool_pad"}
EPS = 1e-5


def layers(topology: dict) -> list:
    """The conv layers in order, as the program numbers them: the stem,
    then two per basic block. Each is a dict of ``n_out``, ``kernel``,
    ``stride``, ``pool``, ``pool_stride``, ``pool_pad`` and ``join``
    (``none``, ``identity`` or ``project``, on the block's second conv).
    A key not implemented here is an error."""
    unknown = (set(topology) - TOPOLOGY_KEYS) | (set(topology["stem"]) - STEM_KEYS)
    if unknown:
        raise ValueError(f"keys {sorted(unknown)} not implemented by the resnet family")
    stem = topology["stem"]
    out = [{**stem, "join": "none"}]
    c = stem["n_out"]
    for si, n in enumerate(topology["widths"]):
        for bi in range(topology["blocks_per_stage"]):
            s = 2 if si and not bi else 1
            join = "project" if (s != 1 or c != n) else "identity"
            plain = {"pool": 0, "pool_stride": 0, "pool_pad": 0}
            out.append({"n_out": n, "kernel": 3, "stride": s, **plain, "join": "none"})
            out.append({"n_out": n, "kernel": 3, "stride": 1, **plain, "join": join})
            c = n
    return out


def frame_shape(topology: dict) -> tuple:
    """(H, W, C) of one input frame."""
    return (*topology["input_hw"], topology["input_channels"])


def conv_shapes(topology: dict) -> list:
    """Per conv layer ``(C_in, N_out, K, H_conv, W_conv, proj)``: the conv's
    output size before its max-pool, and for a ``project`` join the
    projection's input channels (else 0)."""
    h, w = topology["input_hw"]
    c = topology["input_channels"]
    out, ins = [], []
    for layer in layers(topology):
        ins.append(c)
        s = layer["stride"]
        hc, wc = -(-h // s), -(-w // s)
        proj = ins[-2] if layer["join"] == "project" else 0
        out.append((c, layer["n_out"], layer["kernel"], hc, wc, proj))
        h, w = hc, wc
        if layer["pool"]:
            p, ps, pp = layer["pool"], layer["pool_stride"], layer["pool_pad"]
            h, w = (h + 2 * pp - p) // ps + 1, (w + 2 * pp - p) // ps + 1
        c = layer["n_out"]
    return out


def _layer_macs(shape) -> int:
    c, n, k, h, w, proj = shape
    return c * n * k * k * h * w + proj * n * h * w


def layer_ops(topology: dict) -> list:
    """Operations of each conv layer for one frame, 2 per multiply-add,
    its projection shortcut included. Bias, batch norm, join, pool and
    activation are not counted."""
    return [2 * _layer_macs(s) for s in conv_shapes(topology)]


def head_ops(topology: dict) -> int:
    """Operations of the dense layer for one frame: ``2 * C * classes``
    on the globally pooled features."""
    return 2 * topology["widths"][-1] * topology["n_classes"]


def frame_ops(topology: dict) -> int:
    """Operations the whole step spends on one frame (ResNet-18 at 224 px:
    3,627,122,688 in the convs, projections included, plus 1,024,000 in
    the head = 3,628,146,688)."""
    return sum(layer_ops(topology)) + head_ops(topology)


def group_ops(topology: dict, first: int, last: int) -> int:
    """Operations per frame of conv layers ``first..last`` (inclusive)."""
    return sum(layer_ops(topology)[first : last + 1])


def group_bytes(topology: dict, first: int, last: int, n_frames: int,
                act_bytes: int) -> int:
    """Bytes a fused kernel of conv layers ``first..last`` must move for
    ``n_frames`` frames when its intermediates and shortcuts stay on
    chip: each frame's input map read once (``act_bytes`` an element),
    its output map written once as float32, and the layers' weights
    (``act_bytes``), biases (4) and projections read once per call."""
    shapes = conv_shapes(topology)
    c0, _, _, _, _, _ = shapes[first]
    if first == 0:
        h0, w0 = topology["input_hw"]
    else:
        _, _, _, h0, w0, _ = shapes[first - 1]
        prev = layers(topology)[first - 1]
        if prev["pool"]:
            p, ps, pp = prev["pool"], prev["pool_stride"], prev["pool_pad"]
            h0, w0 = (h0 + 2 * pp - p) // ps + 1, (w0 + 2 * pp - p) // ps + 1
    _, n, _, h, w, _ = shapes[last]
    weights = sum(
        (k * k * c * nn + p * nn) * act_bytes + (nn * (2 if p else 1)) * 4
        for c, nn, k, _, _, p in shapes[first : last + 1]
    )
    return n_frames * (h0 * w0 * c0 * act_bytes + h * w * n * 4) + weights


def _param_shapes(topology: dict) -> dict:
    conv = []
    for (c, n, k, _, _, proj) in conv_shapes(topology):
        leaf = {"w": (k, k, c, n), "b": (n,), "gamma": (n,), "beta": (n,),
                "mean": (n,), "var": (n,)}
        if proj:
            leaf["proj"] = {"w": (1, 1, proj, n), "b": (n,), "gamma": (n,),
                            "beta": (n,), "mean": (n,), "var": (n,)}
        conv.append(leaf)
    c = topology["widths"][-1]
    return {"conv": conv, "fc": [{"w": (c, topology["n_classes"]),
                                  "b": (topology["n_classes"],)}]}


def make_params(topology: dict, key_words):
    """Random weights on the device, in one jitted call, from the weights
    stream (``inputs.streams``): He-normal kernels (std ``sqrt(2 /
    fan_in)``) and dense weights, zero conv biases (the published convs
    have none), dense bias of std 0.1, and batch-norm statistics drawn so
    that the stream stays mostly inside the 8-bit grid's +-2: ``gamma``
    uniform in [0.2, 0.5], ``beta`` and ``mean`` normal of std 0.1,
    ``var`` uniform in [0.5, 2]."""
    shapes = _param_shapes(topology)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple)
    )

    def draw(k, path, shp):
        name = str(getattr(path[-1], "key", ""))
        if name == "gamma":
            return jax.random.uniform(k, shp, jnp.float32, 0.2, 0.5)
        if name in ("beta", "mean"):
            return 0.1 * jax.random.normal(k, shp, jnp.float32)
        if name == "var":
            return jax.random.uniform(k, shp, jnp.float32, 0.5, 2.0)
        if name == "b":
            dense = str(getattr(path[0], "key", "")) == "fc"
            return (0.1 if dense else 0.0) * jax.random.normal(k, shp, jnp.float32)
        fan_in = int(np.prod(shp[:-1]))
        std = np.sqrt(2.0 / fan_in).astype(np.float32)
        return std * jax.random.normal(k, shp, jnp.float32)

    def init(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [draw(k, p, s) for k, (p, s) in zip(keys, paths)]
        )

    key = jax.random.wrap_key_data(jnp.asarray(key_words, jnp.uint32))
    return jax.jit(init)(key)


def _fold_host(w, b, gamma, beta, mean, var):
    w, b, gamma, beta, mean, var = (
        np.asarray(a, np.float64) for a in (w, b, gamma, beta, mean, var)
    )
    g = gamma / np.sqrt(var + EPS)
    return (w * g).astype(np.float32), ((b - mean) * g + beta).astype(np.float32)


def _fold(p: dict) -> dict:
    """Batch norm folded into the conv: ``g = gamma / sqrt(var + eps)``,
    kernel ``w * g``, bias ``(b - mean) * g + beta``, computed on the host
    in float64 and rounded once to float32, as offline folding does. In
    float32 on the device the fold's last bit depends on how XLA fuses
    it, and that bit moves some of the 8-bit codes."""
    out = (jax.ShapeDtypeStruct(p["w"].shape, jnp.float32),
           jax.ShapeDtypeStruct(p["b"].shape, jnp.float32))
    w, b = jax.pure_callback(
        _fold_host, out, p["w"], p["b"], p["gamma"], p["beta"], p["mean"], p["var"]
    )
    return {"w": w.astype(p["w"].dtype), "b": b.astype(p["b"].dtype)}


def forward(params, x, topology: dict, *, fake_quant_bits, dot_operands):
    """ResNet's forward pass in plain ``jax.numpy``. In float every batch
    norm is its own op after its conv. Quantized (``fake_quant_bits``),
    what deployments quantize is quantized: each kernel with its batch
    norm folded in, and its bias, on their own power-of-two grids, the
    frame and each activation on the stream grid; a join adds its
    shortcut before the ReLU and the sum is quantized after it. The
    globally pooled features are not requantized."""
    hp = jax.lax.Precision.HIGHEST

    def rd(v):
        if dot_operands == "bfloat16":
            return v.astype(jnp.bfloat16).astype(v.dtype)
        return v

    quant = fake_quant_bits is not None

    def prep(p):
        if not quant:
            return p
        return {k: _pow2_fake_quant(v, fake_quant_bits) for k, v in _fold(p).items()}

    def conv(h, p, stride, padding):
        y = jax.lax.conv_general_dilated(
            rd(h), rd(p["w"]), window_strides=(stride, stride), padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hp,
        ) + p["b"]
        if not quant:
            y = (y - p["mean"]) / jnp.sqrt(p["var"] + EPS) * p["gamma"] + p["beta"]
        return y

    if quant:
        x = _grid(x, fake_quant_bits)
    h, ins = x, []
    specs = layers(topology)
    for i, (layer, p) in enumerate(zip(specs, params["conv"])):
        ins.append(h)
        h = conv(h, prep(p), layer["stride"], "SAME")
        if layer["join"] == "identity":
            h = h + ins[i - 1]
        elif layer["join"] == "project":
            s = specs[i - 1]["stride"] * layer["stride"]
            h = h + conv(ins[i - 1], prep(p["proj"]), s, "VALID")
        if layer["pool"]:
            k, ks, pp = layer["pool"], layer["pool_stride"], layer["pool_pad"]
            h = jax.lax.reduce_window(
                h, jnp.array(-jnp.inf, h.dtype), jax.lax.max, (1, k, k, 1),
                (1, ks, ks, 1), ((0, 0), (pp, pp), (pp, pp), (0, 0)),
            )
        h = jnp.maximum(h, 0)
        if quant:
            h = _grid(h, fake_quant_bits)
    h = jnp.mean(h, axis=(1, 2))
    fc = params["fc"][0]
    if quant:
        fc = {k: _pow2_fake_quant(v, fake_quant_bits) for k, v in fc.items()}
    return jnp.dot(rd(h), rd(fc["w"]), precision=hp) + fc["b"]


def system(cfg: dict, params):
    """The plan ``Engine`` serves: the configuration compiled by the
    program's ``compile_dhm``."""
    from repro.core.dhm import QuantSpec, compile_dhm
    from repro.models.cnn import CNNTopology, ConvLayerSpec

    t = cfg["topology"]
    specs = tuple(
        ConvLayerSpec(
            n_out=layer["n_out"], kernel=layer["kernel"], padding="SAME",
            stride=layer["stride"], pool=layer["pool"],
            pool_stride=layer["pool_stride"] or None,
            pool_pad=layer["pool_pad"], act="relu", bn=True, join=layer["join"],
        )
        for layer in layers(t)
    )
    topo = CNNTopology(
        name=cfg["name"], input_hw=tuple(t["input_hw"]),
        input_channels=t["input_channels"], conv_layers=specs, fc_dims=(),
        n_classes=t["n_classes"], global_pool=True,
    )
    return compile_dhm(topo, params, quant=QuantSpec(**cfg["quant"]))


def kernel_calls(plan) -> int:
    """Mosaic kernels (``tpu_custom_call``) the compiled forward must
    hold: one per fusion group."""
    return len(plan.fusion_groups)
