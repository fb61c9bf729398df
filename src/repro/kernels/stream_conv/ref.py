"""Pure-jnp oracles for the streaming conv kernels.

``stream_conv2d_ref`` is a plain VALID conv2d (NHWC x HWIO -> NHWC) with a
configurable stride — the semantics of the paper's dataflow conv engine
once the stream is re-assembled into a frame. ``stream_conv_block_ref``
composes the UNFUSED actor chain (conv, + bias, activation, NxN/stride-s
max-pool, feature-stream fake-quant) as separate XLA ops; the fused
kernels must match it exactly. The quantization step here deliberately
goes through ``fake_quant_ste`` (the model-level reference) so the
in-kernel epilogue is tested against an independent rendering of the same
Q-format, and the pooling goes through ``lax.reduce_window`` so the
epilogue's shifted-strided-view pool is tested against an independent
rendering too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant.fixed_point import (
    FixedPointSpec,
    fake_quant_ste,
    quantize_fixed,
)
from repro.kernels.stream_conv.epilogue import ACTS, normalize_pool
from repro.kernels.stream_conv.halo import BLOCK_SPAN


def stream_conv2d_ref(
    x: jax.Array, w: jax.Array, *, stride: int = 1
) -> jax.Array:
    """x: (B, H, W, C); w: (K, K, C, N). VALID, stride ``stride`` ->
    (B, (H-K)//s+1, (W-K)//s+1, N)."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def stream_conv_block_ref(
    x: jax.Array,  # (B, H, W, C)
    w: jax.Array,  # (K, K, C, N) HWIO
    b: jax.Array,  # (N,)
    *,
    padding: str = "VALID",
    stride: int = 1,
    act: str = "none",
    pool: int = 0,
    pool_stride: int | None = None,
    pool_pad: int = 0,
    act_bits: int | None = None,
    int8_scales=None,
    residual=None,
) -> jax.Array:
    """Unfused conv -> bias (+ ``residual``) -> act -> NxN/stride-s
    max-pool (``pool_pad`` -inf rows/cols per side) -> fake-quant
    reference composition.

    ``int8_scales`` (an ``epilogue.Int8Scales``) switches the conv to the
    true-integer rendering: the input is quantized onto its stream grid as
    int8 codes (exact for on-grid values), ``w`` must already be int8
    weight codes, and the conv contracts integers into an int32
    accumulator (``preferred_element_type``) that one exact pow2 multiply
    dequantizes back to fp32 before the bias/act/pool/quant chain.
    """
    if act not in ACTS:
        raise ValueError(f"unknown act {act!r}")
    pw, ps = normalize_pool(pool, pool_stride)
    if int8_scales is not None:
        if not jnp.issubdtype(w.dtype, jnp.signedinteger):
            raise ValueError(
                f"int8_scales given but weights are {w.dtype}, not int codes"
            )
        qx = (
            quantize_fixed(x, int8_scales.in_spec).astype(jnp.int8)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else x
        )
        y = jax.lax.conv_general_dilated(
            qx,
            w.astype(jnp.int8),
            window_strides=(stride, stride),
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32,
        )
        y = y.astype(jnp.float32) * int8_scales.deq_scale
    else:
        y = jax.lax.conv_general_dilated(
            x.astype(jnp.float32),
            w.astype(jnp.float32),
            window_strides=(stride, stride),
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    y = y + b.astype(jnp.float32)
    if residual is not None:
        y = y + residual
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "tanh":
        y = jnp.tanh(y)
    if pw:
        y = jax.lax.reduce_window(
            y,
            -jnp.inf,
            jax.lax.max,
            window_dimensions=(1, pw, pw, 1),
            window_strides=(1, ps, ps, 1),
            padding=((0, 0), (pool_pad, pool_pad), (pool_pad, pool_pad), (0, 0)),
        )
    if act_bits is not None:
        y = fake_quant_ste(y, FixedPointSpec(bits=act_bits, frac_bits=act_bits - 2))
    return y


def stream_conv_pyramid_ref(
    x: jax.Array,
    weights,  # per layer (K, K, C, N)
    biases,  # per layer (N,)
    *,
    layers,  # PyramidLayer per layer (padding/stride/act/pool/pool_stride)
    act_bits=None,  # int | None | per-layer tuple
    int8_scales=None,  # None | per-layer tuple of Int8Scales
    projections=None,  # None | per layer None or the join's (w, b)
) -> jax.Array:
    """Reference rendering of a fusion group: the plain per-layer
    ``stream_conv_block_ref`` chain. Fusion is a scheduling decision, not
    a semantic one — the group's math is exactly the layer composition.
    A layer with a ``join`` adds its block's input (the previous layer's
    input), or that input's 1x1 projection at the block's stride, to its
    conv output before the activation."""
    layers = tuple(layers)
    n = len(layers)
    bits = act_bits if isinstance(act_bits, tuple) else (act_bits,) * n
    ins = []
    for i, (layer, w, b) in enumerate(zip(layers, weights, biases)):
        ins.append(x)
        sc = None if int8_scales is None else int8_scales[i]
        residual = None
        if layer.join != "none":
            residual = ins[i - BLOCK_SPAN + 1]
            if layer.join == "project":
                pw_, pb = projections[i]
                stride = layers[i - 1].stride * layer.stride
                residual = stream_conv_block_ref(
                    residual, pw_, pb, stride=stride, act="none",
                    int8_scales=None if sc is None else sc.proj,
                )
        x = stream_conv_block_ref(
            x, w, b, padding=layer.padding, stride=layer.stride,
            act=layer.act, pool=layer.pool, pool_stride=layer.pool_stride,
            act_bits=bits[i], int8_scales=sc, residual=residual,
        )
    return x
