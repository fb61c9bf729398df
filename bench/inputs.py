"""Weights and frames, made from ``--seed`` and nothing else.

One seed gives the same weights, frames and arrival schedule on every
run. Weights are made on the device in one jitted call, in float32 as the
plans take them; frames are made on the host, where the program's
``Engine.submit`` takes them.
"""
from __future__ import annotations

import numpy as np

from costs import feature_shape


def streams(seed: int) -> dict:
    """Independent generators for weights, frames and traffic, from one
    seed of any size (``SeedSequence`` takes integers wider than 64 bits)."""
    ss = np.random.SeedSequence(seed)
    w, f, t = ss.spawn(3)
    return {
        "weights": w.generate_state(2, np.uint32),
        "frames": np.random.default_rng(f),
        "traffic": np.random.default_rng(t),
    }


def param_shapes(topology: dict) -> dict:
    """Leaf shapes in the program's layout: conv kernels HWIO (K, K, C, N),
    dense weights (in, out)."""
    conv, c = [], topology["input_channels"]
    for layer in topology["conv_layers"]:
        k, n = layer["kernel"], layer["n_out"]
        conv.append({"w": (k, k, c, n), "b": (n,)})
        c = n
    h, w, c = feature_shape(topology)
    dims = [h * w * c, *topology["fc_dims"], topology["n_classes"]]
    fc = [{"w": (a, b), "b": (b,)} for a, b in zip(dims[:-1], dims[1:])]
    return {"conv": conv, "fc": fc}


def make_params(topology: dict, key_words):
    """Random weights on the device, in one jitted call: He-normal kernels
    (std ``sqrt(2 / fan_in)``) and biases of std 0.1, so that the bias
    path carries values."""
    import jax
    import jax.numpy as jnp

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


def frame_pool(topology: dict, grid_bits: int, n: int, rng) -> np.ndarray:
    """``n`` frames (n, H, W, C) float32 on the input grid Q(b, b - 2):
    standard normal values rounded to the grid step and clipped to the
    signed ``b``-bit range, as a camera's quantized pixels would arrive."""
    h, w = topology["input_hw"]
    step = 2.0 ** (grid_bits - 2)
    codes = np.round(
        rng.standard_normal((n, h, w, topology["input_channels"]), np.float32)
        * step
    )
    lim = 2 ** (grid_bits - 1)
    return (np.clip(codes, -lim, lim - 1) / step).astype(np.float32)
