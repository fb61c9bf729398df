"""Public wrappers for the streaming conv kernels.

``stream_conv2d`` is the bare conv (kept for API compatibility and as the
benchmark subject); ``stream_conv_block`` is the fused
conv -> bias -> activation -> max-pool actor chain — the DHM pipeline
stage — used by the CNN model, the DHM pipeline stage bodies, and the
examples. Both accept a conv ``stride``; the block additionally takes a
``(pool, pool_stride)`` pair (square window, sliding stride; ``pool=2``
keeps meaning the classic 2x2/stride-2) and ``block_w`` column blocking
for frames wider than VMEM.

Backends (validated; see ``repro.kernels.backends``):
  - ``pallas``:           compiled. Mosaic-compiled Pallas on TPU; on
                          platforms without compiled Pallas (XLA:CPU) the
                          same row-block single-matmul algorithm is lowered
                          through XLA (``xla.py``). This is the default.
  - ``pallas_interpret``: the Pallas kernel through the interpreter — the
                          correctness oracle.
  - ``ref``:              plain ``lax.conv`` composition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backends import (
    DEFAULT_BACKEND,
    compiled_pallas_available,
    validate_backend,
)
from repro.kernels.stream_conv.conv import (
    residual_kernel,
    stream_conv_fused_pallas,
    stream_conv_pyramid_pallas,
)
from repro.kernels.stream_conv.halo import as_pyramid_layers
from repro.kernels.stream_conv.ref import (
    stream_conv_block_ref,
    stream_conv_pyramid_ref,
)
from repro.kernels.stream_conv.xla import (
    stream_conv_fused_xla,
    stream_conv_pyramid_xla,
)


def _pad_same(x: jax.Array, k: int, stride: int = 1) -> jax.Array:
    """SAME pads on the host side (the FPGA engine pads the pixel stream
    at frame edges). XLA's SAME convention — per dim, total = max((ceil(d/s)
    - 1)*s + k - d, 0), low = total//2, high = total - low — so strided and
    even-K results match the lax.conv reference backend exactly."""

    def split(d: int) -> tuple:
        out = -(-d // stride)
        tot = max((out - 1) * stride + k - d, 0)
        lo = tot // 2
        return lo, tot - lo

    ph = split(x.shape[1])
    pw = split(x.shape[2])
    return jnp.pad(x, ((0, 0), ph, pw, (0, 0)))


LANES = 128  # lane width of a TPU vector register


def _space_to_depth(x: jax.Array, w: jax.Array, stride: int) -> tuple:
    """A VALID stride-``s`` conv as the equal stride-1 conv on the frame
    folded s x s into channels: ``(B, H, W, C) -> (B, H/s, W/s, s*s*C)``,
    the kernel zero-padded to ``K' * s`` taps (``K' = ceil(K / s)``) and
    folded the same way to ``(K', K', s*s*C, N)``. Exact (the same
    products, regrouped). A narrow frame then fills s*s times more lanes,
    and the kernel assembles ``K'^2`` unit-stride taps instead of ``K^2``
    strided ones: ResNet's 7x7/2 stem on 3 channels becomes a 4x4 conv
    on 12."""
    b, h, wd, c = x.shape
    k, n, s = w.shape[0], w.shape[3], stride
    kq = -(-k // s)
    h_out, w_out = (h - k) // s + 1, (wd - k) // s + 1
    rows, cols = (h_out - 1 + kq) * s, (w_out - 1 + kq) * s
    x = jnp.pad(x[:, :rows, :cols], (
        (0, 0), (0, max(0, rows - h)), (0, max(0, cols - wd)), (0, 0)
    ))
    x = x.reshape(b, rows // s, s, cols // s, s, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, rows // s, cols // s, s * s * c)
    w = jnp.pad(w, ((0, kq * s - k), (0, kq * s - k), (0, 0), (0, 0)))
    w = w.reshape(kq, s, kq, s, c, n).transpose(0, 2, 1, 3, 4, 5)
    return x, w.reshape(kq, kq, s * s * c, n)


def _validate_int8(int8_scales, act_bits, w) -> None:
    if int8_scales is None:
        return
    if act_bits is None:
        raise ValueError("int8_scales requires act_bits (the stream grid)")
    if not jnp.issubdtype(w.dtype, jnp.signedinteger):
        raise ValueError(
            f"int8_scales requires int8 weight codes, got {w.dtype} — bake "
            "weights with quantize_fixed(w, dynamic_spec(w, bits))"
        )


def _fused_dispatch(
    x, w, b, *, padding, stride, act, pool, pool_stride, act_bits,
    int8_scales, out_dtype, backend, block_r, block_w, block_c, block_n,
    pool_pad=0,
):
    k = w.shape[0]
    if w.shape[1] != k:
        raise ValueError(f"only square kernels, got {w.shape}")
    validate_backend(backend)
    _validate_int8(int8_scales, act_bits, w)
    if backend == "ref":
        return stream_conv_block_ref(
            x, w, b, padding=padding, stride=stride, act=act, pool=pool,
            pool_stride=pool_stride, pool_pad=pool_pad, act_bits=act_bits,
            int8_scales=int8_scales,
        ).astype(out_dtype)
    if padding == "SAME":
        x = _pad_same(x, k, stride)
    elif padding != "VALID":
        raise ValueError(padding)
    if int8_scales is not None and jnp.issubdtype(x.dtype, jnp.floating):
        # Quantize onto the input stream grid OUTSIDE the kernel call: the
        # resident frame is int8 codes (1 byte/element — what the fusion
        # planner charges), and pad zeros above are code 0 == value 0.
        from repro.core.quant.fixed_point import quantize_fixed

        x = quantize_fixed(x, int8_scales.in_spec).astype(jnp.int8)
    if stride > 1 and x.shape[-1] * stride * stride <= LANES:
        x, w = _space_to_depth(x, w, stride)
        k, stride = w.shape[0], 1
    w_taps = w.reshape(k * k, w.shape[2], w.shape[3])
    if backend == "pallas" and not compiled_pallas_available():
        # Compiled fallback: identical algorithm, lowered through XLA.
        # Row blocks there are sized from a memory budget, not VMEM, so
        # the block_* tuning knobs are Pallas-only.
        return stream_conv_fused_xla(
            x, w_taps, b, k=k, stride=stride, act=act, pool=pool,
            pool_stride=pool_stride, pool_pad=pool_pad, act_bits=act_bits,
            int8_scales=int8_scales, out_dtype=out_dtype,
        )
    return stream_conv_fused_pallas(
        x,
        w_taps,
        b,
        k=k,
        stride=stride,
        act=act,
        pool=pool,
        pool_stride=pool_stride,
        pool_pad=pool_pad,
        act_bits=act_bits,
        int8_scales=int8_scales,
        block_r=block_r,
        block_w=block_w,
        block_c=block_c,
        block_n=block_n,
        out_dtype=out_dtype,
        interpret=(backend == "pallas_interpret"),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "stride", "backend", "out_dtype", "block_r", "block_w",
        "block_c", "block_n",
    ),
)
def stream_conv2d(
    x: jax.Array,  # (B, H, W, C)
    w: jax.Array,  # (K, K, C, N) HWIO
    *,
    padding: str = "VALID",
    stride: int = 1,
    out_dtype=jnp.float32,
    backend: str = DEFAULT_BACKEND,
    block_r: int = 8,
    block_w: int = 0,
    block_c: int = 0,
    block_n: int = 0,
) -> jax.Array:
    """Streaming conv2d, stride ``stride``, no epilogue. SAME pads on the
    host side."""
    zero_b = jnp.zeros((w.shape[3],), jnp.float32)
    return _fused_dispatch(
        x, w, zero_b,
        padding=padding, stride=stride, act="none", pool=0, pool_stride=None,
        act_bits=None, int8_scales=None, out_dtype=out_dtype, backend=backend,
        block_r=block_r, block_w=block_w, block_c=block_c, block_n=block_n,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "stride", "act", "pool", "pool_stride", "pool_pad",
        "act_bits", "int8_scales", "backend", "out_dtype", "block_r",
        "block_w", "block_c", "block_n",
    ),
)
def stream_conv_block(
    x: jax.Array,  # (B, H, W, C)
    w: jax.Array,  # (K, K, C, N) HWIO
    b: jax.Array,  # (N,)
    *,
    padding: str = "VALID",
    stride: int = 1,
    act: str = "relu",
    pool: int = 2,
    pool_stride: int | None = None,
    pool_pad: int = 0,
    act_bits: int | None = None,
    int8_scales=None,
    out_dtype=jnp.float32,
    backend: str = DEFAULT_BACKEND,
    block_r: int = 8,
    block_w: int = 0,
    block_c: int = 0,
    block_n: int = 0,
) -> jax.Array:
    """Fused conv -> bias -> act -> NxN/stride-s-max-pool block (one DHM
    pipeline stage). ``pool=0`` disables pooling, ``pool_stride=None``
    means window == stride (so ``pool=2`` is the classic 2x2/2),
    ``pool_pad`` pads the pooled map with -inf on every side,
    ``act='none'`` the activation; ``act_bits`` quantizes the output
    feature stream inside the same fused epilogue (the paper's quantized
    pixel flow — no separate HBM pass).

    ``int8_scales`` (a static ``epilogue.Int8Scales``) switches all
    backends to true integer arithmetic: ``w`` must be int8 weight codes,
    the input is quantized onto its stream grid (exact for on-grid
    values), and the conv contracts int8 x int8 -> int32 before the
    requantizing epilogue — fp32 values on the ``act_bits`` grid out, so
    the call boundary contract is unchanged."""
    return _fused_dispatch(
        x, w, b,
        padding=padding, stride=stride, act=act, pool=pool,
        pool_stride=pool_stride, act_bits=act_bits, int8_scales=int8_scales,
        out_dtype=out_dtype, backend=backend, pool_pad=pool_pad,
        block_r=block_r, block_w=block_w, block_c=block_c, block_n=block_n,
    )


def stream_conv_pyramid(
    x: jax.Array,  # (B, H, W, C0)
    weights,  # sequence of (K, K, C, N) HWIO, one per layer
    biases,  # sequence of (N,), one per layer
    *,
    layers,  # sequence of layer specs (padding/stride/act/pool[/pool_stride])
    act_bits=None,  # int | None | per-layer tuple
    int8_scales=None,  # None | per-layer tuple of Int8Scales
    block_rows: int = 0,
    out_dtype=jnp.float32,
    backend: str = DEFAULT_BACKEND,
    projections=None,  # None | per layer None or a project join's (w, b)
    first_layer: int | None = None,  # plan index of layers[0]; joins need it
) -> jax.Array:
    """Cross-layer fused conv pyramid: a whole fusion group of consecutive
    conv -> bias -> act -> pool layers as ONE kernel invocation, with all
    inter-layer feature slabs kept on-chip (VMEM scratch on the Pallas
    path) — the paper's no-external-memory dataflow property extended
    across layer boundaries.

    ``layers`` is a sequence of duck-typed layer specs (``ConvLayerSpec``
    or anything with ``padding``/``act``/``pool`` and the optional
    generalized fields); ``weights``/``biases`` are the matching per-layer
    tensors. ``block_rows`` sets the final-output rows streamed per block
    on the Pallas path (0 = whole frame; the input halo per block is the
    composed per-layer requirement from ``halo.group_geometry``). The
    ``pallas`` backend lowers through Mosaic on TPU and through the
    one-closure XLA rendering elsewhere; ``pallas_interpret`` runs the
    exact multi-layer kernel program as the oracle; ``ref`` is the
    unfused per-layer chain.

    ``act_bits`` may be a per-layer tuple (mixed-bitwidth plans);
    ``int8_scales`` (per-layer tuple of ``Int8Scales``) selects true
    integer arithmetic: the frame is quantized onto layer 0's stream grid
    before the kernel (1-byte resident frame), interior layers consume
    and emit int8 stream codes, and each ``Int8Scales.in_bits`` must name
    the previous layer's ``act_bits`` (the code chain contract).

    A layer whose spec carries a residual ``join`` closes a basic block:
    its shortcut (the block input, or for ``project`` its 1x1 projection
    by ``projections[i]``, under ``int8_scales[i].proj``) is added before
    its activation, and the group lowers through the residual kernel
    (``conv.residual_kernel``), the same body under a name of its own that
    gives the plan's indices of the group's layers: ``first_layer`` is
    that of ``layers[0]``, and a group with a join cannot go without it.
    """
    validate_backend(backend)
    weights = tuple(weights)
    biases = tuple(biases)
    layers = tuple(layers)
    if not weights or len(weights) != len(biases) or len(weights) != len(layers):
        raise ValueError(
            f"pyramid needs matching layers/weights/biases, got "
            f"{len(layers)}/{len(weights)}/{len(biases)}"
        )
    for li, w in enumerate(weights):
        if w.ndim != 4 or w.shape[0] != w.shape[1]:
            raise ValueError(
                f"pyramid layer {li}: only square HWIO kernels, got {w.shape}"
            )
    bits = (
        act_bits if isinstance(act_bits, tuple)
        else (act_bits,) * len(layers)
    )
    if len(bits) != len(layers):
        raise ValueError(
            f"act_bits tuple has {len(bits)} entries for "
            f"{len(layers)} layers"
        )
    if int8_scales is not None:
        int8_scales = tuple(int8_scales)
        if len(int8_scales) != len(layers):
            raise ValueError(
                f"int8_scales has {len(int8_scales)} entries for "
                f"{len(layers)} layers"
            )
        for li, (sc, w) in enumerate(zip(int8_scales, weights)):
            _validate_int8(sc, bits[li], w)
            if li and sc.in_bits != bits[li - 1]:
                raise ValueError(
                    f"pyramid layer {li}: in_bits={sc.in_bits} must equal "
                    f"the previous layer's act_bits={bits[li - 1]} (the "
                    "inter-layer code chain)"
                )
    pyr = as_pyramid_layers(layers)
    projections = (
        (None,) * len(layers) if projections is None else tuple(projections)
    )
    for li, (layer, pj) in enumerate(zip(pyr, projections)):
        if (layer.join == "project") != (pj is not None):
            raise ValueError(
                f"pyramid layer {li}: a project join needs its (w, b) "
                f"projection, and only it takes one (join={layer.join!r})"
            )
        if int8_scales is not None and (
            (layer.join == "project") != (int8_scales[li].proj is not None)
        ):
            raise ValueError(
                f"pyramid layer {li}: a project join needs its own "
                "Int8Scales (proj), and only it takes one"
            )
    projs = tuple(pj for pj in projections if pj is not None)
    joined = any(layer.join != "none" for layer in pyr)
    if joined and first_layer is None:
        raise ValueError(
            "a pyramid group with a residual join needs its first_layer: "
            "its kernel is named after the plan layers it computes"
        )
    if backend == "ref":
        return stream_conv_pyramid_ref(
            x, weights, biases, layers=pyr, act_bits=bits,
            int8_scales=int8_scales, projections=projections,
        ).astype(out_dtype)
    if backend == "pallas" and not compiled_pallas_available():
        return stream_conv_pyramid_xla(
            x, weights, biases, layers=pyr, act_bits=bits,
            int8_scales=int8_scales, out_dtype=out_dtype, projections=projs,
        )
    if int8_scales is not None and jnp.issubdtype(x.dtype, jnp.floating):
        # Quantize onto layer 0's stream grid OUTSIDE the pallas_call: the
        # VMEM-resident frame is int8 codes — what the planner charges.
        from repro.core.quant.fixed_point import quantize_fixed

        x = quantize_fixed(x, int8_scales[0].in_spec).astype(jnp.int8)
    kw = dict(
        layers=pyr, act_bits=bits, int8_scales=int8_scales,
        block_rows=block_rows, out_dtype=out_dtype,
        interpret=(backend == "pallas_interpret"),
    )
    if joined:
        kernel = residual_kernel(first_layer, first_layer + len(pyr) - 1)
        return kernel(x, weights, biases, projs, **kw)
    return stream_conv_pyramid_pallas(x, weights, biases, **kw)
