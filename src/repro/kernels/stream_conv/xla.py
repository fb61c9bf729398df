"""XLA rendering of the fused streaming conv — the compiled path on
platforms where Mosaic/Pallas compilation is unavailable (XLA:CPU only
supports the Pallas interpreter).

Same algorithm as the Pallas kernel in ``conv.py``, including the row
blocking: each row block's K*K stride-s shifted views are assembled into
one tall operand and contracted against the flattened (K*K*C, N) tap
matrix in a SINGLE matmul per row block, then the shared bias ->
activation -> NxN/stride-s max-pool epilogue runs in-block (overlapping
pool windows re-compute their ``pool - pool_stride`` boundary conv rows
inside each block, exactly like the Pallas kernel's halo). No ``lax.conv``,
and no unbounded im2col: R is sized so the per-block operand stays under a
fixed byte budget (the XLA analogue of the kernel's VMEM blocking), so
arbitrarily large batch/feature-map products cannot blow up memory. Small
workloads fit one block and skip the ``lax.map`` loop entirely. Width
blocking is a VMEM concern, not an XLA one — the whole output width is
processed per row block here (``block_w`` is Pallas-only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.padding import round_up
from repro.kernels.stream_conv.epilogue import (
    apply_epilogue,
    normalize_pool,
    pool_out_dim,
    validate_epilogue,
)

# Per-block im2col operand budget. ~128 MB: big enough that realistic
# single-frame layers run as one fused block, small enough that batched
# CIFAR-scale layers (which would need GBs unblocked) get row-blocked.
_BLOCK_BYTES_BUDGET = 128 * 1024 * 1024


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "stride", "act", "pool", "pool_stride", "pool_pad", "act_bits",
        "int8_scales", "out_dtype",
    ),
)
def stream_conv_fused_xla(
    x: jax.Array,  # (B, H, W, C), already SAME-padded if needed
    w_taps: jax.Array,  # (K*K, C, N)
    bias: jax.Array,  # (N,)
    *,
    k: int,
    stride: int = 1,
    act: str = "none",
    pool: int = 0,
    pool_stride: int | None = None,
    pool_pad: int = 0,
    act_bits: int | None = None,
    int8_scales=None,
    out_dtype=jnp.float32,
) -> jax.Array:
    if int8_scales is not None:
        # True-int8 rendering: quantize a float input onto its stream grid
        # (int8 codes; a no-op representation change for on-grid values —
        # pre-quantized int8 frames pass straight through), contract
        # integer codes into an int32 accumulator, dequantize with one
        # exact pow2 multiply. Forward-only (the fp32 path keeps QAT).
        if not jnp.issubdtype(w_taps.dtype, jnp.signedinteger):
            raise ValueError(
                f"int8_scales given but w_taps are {w_taps.dtype}, "
                "not int codes"
            )
        if jnp.issubdtype(x.dtype, jnp.floating):
            from repro.core.quant.fixed_point import quantize_fixed

            x = quantize_fixed(x, int8_scales.in_spec).astype(jnp.int8)
    b, h, wd, c = x.shape
    kk, c2, n = w_taps.shape
    if kk != k * k or c2 != c:
        raise ValueError(f"w_taps {w_taps.shape} inconsistent with k={k}, C={c}")
    if stride < 1:
        raise ValueError(f"conv stride must be >= 1, got {stride}")
    validate_epilogue(act, pool, pool_stride, act_bits)
    pw, ps = normalize_pool(pool, pool_stride)
    s = stride
    h_out, w_out = (h - k) // s + 1, (wd - k) // s + 1
    if h_out <= 0 or w_out <= 0:
        raise ValueError(f"image {h}x{wd} too small for k={k}, stride={s}")
    if pw and (h_out < pw or w_out < pw):
        raise ValueError(
            f"conv output {h_out}x{w_out} too small for {pw}x{pw} pool"
        )
    # A padded pool: pool_pad extra conv rows/cols per side (the frame
    # shifted by pool_pad * stride), masked to -inf before the pool.
    pp = pool_pad
    real_hw = (h_out, w_out)
    if pp:
        x = jnp.pad(x, ((0, 0), (pp * s, 0), (pp * s, pp * s), (0, 0)))
        h_out, w_out = h_out + 2 * pp, w_out + 2 * pp
        h = x.shape[1]

    # Row block from the byte budget: largest R (multiple of the pool
    # stride) whose (B, R, W_out, K*K, C) f32 operand fits.
    overlap = max(0, pw - ps) if pw else 0
    mult = ps if pw else 1
    row_bytes = max(1, b * w_out * k * k * c * 4)
    r = max(mult, (_BLOCK_BYTES_BUDGET // row_bytes) // mult * mult)
    r = min(r, round_up(h_out, mult))
    r_conv = r + overlap  # pool-overlap rows re-computed per block
    r_o = r // ps if pw else r
    h_keep = pool_out_dim(h_out, pw, ps) if pw else h_out
    w_keep = pool_out_dim(w_out, pw, ps) if pw else w_out
    n_rb = -(-h_keep // r_o)

    # Pad rows so every block can read its (r_conv - 1)*s + k input rows
    # (zero rows only feed outputs that are sliced off below).
    blk_in = (r_conv - 1) * s + k
    h_rows = (n_rb - 1) * r * s + blk_in
    if h_rows > h:
        x = jnp.pad(x, ((0, 0), (0, h_rows - h), (0, 0), (0, 0)))
    if int8_scales is not None:
        w_flat = w_taps.reshape(k * k * c, n).astype(jnp.int8)
    else:
        w_flat = w_taps.reshape(k * k * c, n).astype(jnp.float32)

    def block_fn(rb):
        xb = jax.lax.dynamic_slice_in_dim(x, rb * r * s, blk_in, axis=1)
        taps = []
        for ki in range(k):
            for kj in range(k):
                taps.append(
                    xb[
                        :,
                        ki : ki + (r_conv - 1) * s + 1 : s,
                        kj : kj + (w_out - 1) * s + 1 : s,
                        :,
                    ]
                )
        patches = jnp.stack(taps, axis=3)  # (B, r_conv, w_out, k*k, C)
        operand = patches.reshape(b * r_conv * w_out, k * k * c)
        if int8_scales is not None:
            # ONE integer matmul -> int32 accumulator -> exact pow2 dequant.
            yb = jnp.dot(
                operand, w_flat, preferred_element_type=jnp.int32
            ).reshape(b, r_conv, w_out, n)
            yb = yb.astype(jnp.float32) * int8_scales.deq_scale
        else:
            yb = jnp.dot(
                operand.astype(jnp.float32),
                w_flat,
                preferred_element_type=jnp.float32,
            ).reshape(b, r_conv, w_out, n)
        # ste=True: identical forward values, STE gradients — the XLA
        # rendering is the differentiable fused path, so in-kernel stream
        # quantization must not zero out QAT gradients. The int8 path is
        # forward-only (the input rounding has no gradient anyway).
        return apply_epilogue(
            yb, bias, act=act, pool=pool, pool_stride=pool_stride,
            act_bits=act_bits, ste=int8_scales is None,
            pool_pad=(rb * r - pp, -pp, *real_hw) if pp else None,
        )

    if n_rb == 1:
        y = block_fn(0)
    else:
        blocks = jax.lax.map(block_fn, jnp.arange(n_rb))  # (n_rb, B, ...)
        y = jnp.moveaxis(blocks, 0, 1).reshape(b, n_rb * r_o, w_keep, n)
    return y[:, :h_keep].astype(out_dtype)


# ---------------------------------------------------------------------------
# Cross-layer fused pyramid: the whole fusion group as ONE XLA closure.


def _assemble_taps_xla(xp, k: int, s: int, conv_r: int, conv_c: int):
    """Two-step tap assembly on a (B, H, W, C) frame: the Pallas kernel's
    rank-3 ``_assemble_taps`` vmapped over the batch, so the strided-shift
    index arithmetic and the (ki, kj, C) flattening order (which must
    match the HWIO weight reshape exactly) live in ONE place."""
    from repro.kernels.stream_conv.conv import _assemble_taps

    patches = jax.vmap(
        lambda f: _assemble_taps(f, k, s, conv_r, conv_c)
    )(xp)  # (B, conv_r*conv_c, k*k*C)
    b = xp.shape[0]
    c = xp.shape[-1]
    return patches.reshape(b * conv_r * conv_c, k * k * c)


@functools.partial(
    jax.jit,
    static_argnames=("layers", "act_bits", "int8_scales", "out_dtype"),
)
def stream_conv_pyramid_xla(
    x: jax.Array,  # (B, H, W, C0), unpadded
    weights: tuple,  # per layer (K, K, C, N) HWIO
    biases: tuple,  # per layer (N,)
    *,
    layers: tuple,  # PyramidLayer per layer
    act_bits=None,  # int | None | per-layer tuple
    int8_scales=None,  # None | per-layer tuple of Int8Scales
    out_dtype=jnp.float32,
    projections: tuple = (),  # (w, b) per ``project`` join, in layer order
) -> jax.Array:
    """XLA rendering of the fused pyramid — the compiled fallback where
    Mosaic is unavailable. The whole group is one fused XLA graph (this
    function is one jit cache entry): per layer, two-step tap assembly
    feeds a single matmul, then the shared bias -> pool -> act -> quant
    epilogue (``pool_first`` — the ``cnn_apply_reference`` composition
    order, saving the pool factor of activation work). Intermediate
    feature maps stay whole-frame (CPU memory, not VMEM, is the
    constraint here); if a layer's patch operand would exceed the im2col
    byte budget, the closure degrades to the row-blocked per-layer path
    so memory stays bounded (a layer that closes a residual join keeps
    the whole-frame matmul).
    """
    from repro.kernels.stream_conv.halo import BLOCK_SPAN, same_pads

    n_layers = len(layers)
    proj = iter(projections)
    ins = []  # each layer's unpadded input (int8 codes on that path)
    bits = act_bits if isinstance(act_bits, tuple) else (act_bits,) * n_layers
    big = any(
        x.shape[0] * g_h * g_w * k * k * c * 4 > _BLOCK_BYTES_BUDGET
        for (g_h, g_w, k, c) in _pyramid_conv_dims(x.shape, weights, layers)
    )
    for i, (layer, w_t, b_t) in enumerate(zip(layers, weights, biases)):
        k = w_t.shape[0]
        s = layer.stride
        sc = None if int8_scales is None else int8_scales[i]
        if sc is not None and jnp.issubdtype(x.dtype, jnp.floating):
            # Quantize onto the layer's input stream grid before padding:
            # int8 codes thread through SAME pads (code 0 == value 0) and
            # the tap assembly unchanged.
            from repro.core.quant.fixed_point import quantize_fixed

            x = quantize_fixed(x, sc.in_spec).astype(jnp.int8)
        ins.append((x, None if sc is None else sc.in_scale))
        if layer.padding == "SAME":
            ph = same_pads(x.shape[1], s, k)
            pw_ = same_pads(x.shape[2], s, k)
            x = jnp.pad(x, ((0, 0), ph, pw_, (0, 0)))
        if big and layer.join == "none":
            # Bounded-memory fallback: same grouping contract (one jitted
            # closure), row-blocked per-layer kernels inside.
            x = stream_conv_fused_xla(
                x, w_t.reshape(k * k, w_t.shape[2], w_t.shape[3]), b_t,
                k=k, stride=s, act=layer.act, pool=layer.pool,
                pool_stride=layer.pool_stride, act_bits=bits[i],
                int8_scales=sc, out_dtype=jnp.float32,
            )
            continue
        b, h, w, c = x.shape
        conv_r, conv_c = (h - k) // s + 1, (w - k) // s + 1
        operand = _assemble_taps_xla(x, k, s, conv_r, conv_c)
        if sc is not None:
            y = jnp.dot(
                operand,
                w_t.reshape(k * k * c, -1).astype(jnp.int8),
                preferred_element_type=jnp.int32,
            ).reshape(b, conv_r, conv_c, -1)
            y = y.astype(jnp.float32) * sc.deq_scale
        else:
            y = jnp.dot(
                operand.astype(jnp.float32),
                w_t.reshape(k * k * c, -1).astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).reshape(b, conv_r, conv_c, -1)
        residual = None
        if layer.join != "none":
            src, step = ins[i - BLOCK_SPAN + 1]
            st = layers[i - 1].stride * s
            src = src[:, : (conv_r - 1) * st + 1 : st, : (conv_c - 1) * st + 1 : st]
            if layer.join == "identity":
                residual = src if step is None else src.astype(jnp.float32) * step
            else:
                pw_t, pb = next(proj)
                residual = _conv1x1_xla(src, pw_t, pb, None if sc is None else sc.proj)
        # ste=True: the XLA rendering is the differentiable fused path
        # (the int8 rendering is forward-only).
        x = apply_epilogue(
            y, b_t, act=layer.act, pool=layer.pool,
            pool_stride=layer.pool_stride, act_bits=bits[i],
            ste=sc is None, pool_first=True, residual=residual,
        )
    return x.astype(out_dtype)


def _conv1x1_xla(src, w_t, b_t, sc):
    """A projection shortcut on its (already strided) source: one matmul
    (integer into int32 under ``sc``) plus its bias."""
    b, h, w, c = src.shape
    op = src.reshape(b * h * w, c)
    wf = w_t.reshape(c, -1)
    if sc is not None:
        y = jnp.dot(op, wf.astype(jnp.int8), preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * sc.deq_scale
    else:
        y = jnp.dot(op.astype(jnp.float32), wf.astype(jnp.float32))
    return y.reshape(b, h, w, -1) + b_t


def _pyramid_conv_dims(x_shape, weights, layers):
    """Per-layer (conv_rows, conv_cols, k, C) for the pyramid's memory
    guard, read from the shared geometry model (``halo.group_geometry``)
    so the byte guard can never diverge from what the renderers compute."""
    from repro.kernels.stream_conv.halo import group_geometry

    _, h, w, c = x_shape
    geom = group_geometry(
        h, w, c, layers,
        tuple(w_t.shape[0] for w_t in weights),
        tuple(w_t.shape[3] for w_t in weights),
    )
    return [(g.conv_rows, g.conv_cols, g.k, g.in_ch) for g in geom.layers]
