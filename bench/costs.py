"""Operations, bytes and peaks, computed from shapes.

The arithmetic is kept with the benchmark, apart from the program, so the
yardstick cannot move with the code it measures. A configuration file
gives the topology as data (see ``configs/``); nothing here imports the
program.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


LAYER_KEYS = {"n_out", "kernel", "padding", "pool", "act", "stride", "pool_stride"}
REQUIRED = ("n_out", "kernel", "padding", "pool", "act")
PADDINGS = ("SAME", "VALID")
ACTS = ("tanh", "relu", "none")


def layers(topology: dict) -> list:
    """The conv layers as the yardstick reads them, each with its
    ``stride`` (1 where not given) and ``pool_stride`` (the pool window
    where not given). A key this module and the reference do not
    implement, a missing key or an unknown value is an error, so that a
    configuration is never priced or checked as some other network."""
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
        if full["padding"] not in PADDINGS or full["act"] not in ACTS:
            raise ValueError(
                f"conv layer {i}: padding {full['padding']!r} or act "
                f"{full['act']!r} not one of {PADDINGS} and {ACTS}"
            )
        if full["stride"] < 1 or (full["pool"] and full["pool_stride"] < 1):
            raise ValueError(f"conv layer {i}: strides must be at least 1")
        out.append(full)
    return out


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


def conv_ops(topology: dict) -> int:
    """Operations of the conv stack for one frame: 2 per multiply-add,
    ``2 * sum(C_in * N_out * K * K * H_conv * W_conv)``. Bias, pool and
    activation are not counted."""
    return 2 * sum(c * n * k * k * h * w for c, n, k, h, w in conv_shapes(topology))


def head_ops(topology: dict) -> int:
    """Operations of the FC head for one frame: ``2 * sum(d_in * d_out)``
    over the dense layers, from the flattened features through the hidden
    widths to the classes."""
    h, w, c = feature_shape(topology)
    dims = [h * w * c, *topology["fc_dims"], topology["n_classes"]]
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
    h, w = topology["input_hw"]
    frame_in = h * w * topology["input_channels"] * act_bytes
    fh, fw, fc = feature_shape(topology)
    frame_out = fh * fw * fc * 4
    weights = sum(
        k * k * c * n * act_bytes + n * 4
        for c, n, k, _, _ in conv_shapes(topology)
    )
    return n_frames * (frame_in + frame_out) + weights


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error,
    never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]


def peak_ops_per_s(device_kind: str, config: dict) -> float:
    """The peak that prices a configuration: the key its file names
    (``peak``), read from the table for this device kind."""
    return float(peaks(device_kind)[config["peak"]])


def roofline_s(ops: float, n_bytes: float, device_kind: str, config: dict):
    """The least time the chip could take: ``max(ops / peak, bytes /
    bandwidth)``. Returns ``(seconds, bound)`` with bound ``"compute"``
    or ``"memory"``."""
    t_ops = ops / peak_ops_per_s(device_kind, config)
    t_mem = n_bytes / float(peaks(device_kind)["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
