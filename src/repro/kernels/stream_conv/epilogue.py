"""Shared fused epilogue: bias (+ residual join) -> activation ->
NxN/stride-s max-pool -> feature-stream fixed-point quantization.

One definition used by BOTH compiled conv paths (the Pallas kernel body in
``conv.py`` and the XLA fallback in ``xla.py``), so the backends cannot
drift apart. The jnp reference (``ref.py``) deliberately keeps its own
independent composition (``lax.reduce_window`` + ``fake_quant_ste``): it is
the oracle the fused paths are tested against, so it must not share this
code.

Pooling is a square ``pool x pool`` max window sliding with ``pool_stride``
(default: ``pool``, the classic non-overlapping case — ``pool=2`` keeps
meaning 2x2/stride-2). Overlapping windows (``pool_stride < pool``, e.g.
Caffe's cifar10_full 3x3/stride-2) and strided sub-sampling windows
(``pool_stride > pool``) are both legal; output dims follow the VALID
sliding-window rule ``(d - pool) // pool_stride + 1``.

``act_bits`` is the paper's "quantize the pixel flow": the inter-actor
feature stream is a short fixed-point format, so the quantization step
belongs INSIDE the fused actor chain — the block is rounded in VMEM before
write-back, never as a separate pass over the HBM-resident frame. The
Q-format matches the model reference (``FixedPointSpec(bits, bits - 2)``,
the format ``cnn_apply``'s fake-quant composition uses for activations),
and the forward computation — clip(round(y / scale)) * scale — is exactly
``fake_quant_ste``'s forward.

Works on any (..., H, W, N) float32 block — the Pallas kernel calls it on
an (r, w, bn) VMEM block, the XLA path on a (B, r, w, N) row block.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.quant.fixed_point import FixedPointSpec

ACTS = ("none", "relu", "tanh")


@dataclasses.dataclass(frozen=True)
class Int8Scales:
    """Static (hashable — rides jit ``static_argnames``) descriptor of one
    conv layer's true-int8 arithmetic contract.

    ``in_bits`` names the input stream's Q-format (the PRODUCER's
    ``act_bits``; for the first layer, its own stream grid — the plan
    quantizes the incoming frame onto it). ``w_scale`` is the baked
    weights' static pow2 scale: ``int8 codes * w_scale`` reproduces the
    fake-quant weight values bit-exactly, so the integer matmul's int32
    accumulator dequantizes with one exact pow2 multiply
    (``in_scale * w_scale``) back to the fp32 values the fake-quant
    oracle computes.
    """

    in_bits: int
    w_scale: float
    # A ``project`` join's own contract: its input is the block input's
    # stream, its weights the projection's int8 codes.
    proj: "Int8Scales | None" = None

    @property
    def in_spec(self) -> FixedPointSpec:
        return stream_quant_spec(self.in_bits)

    @property
    def in_scale(self) -> float:
        return self.in_spec.scale

    @property
    def deq_scale(self) -> float:
        """int32 accumulator -> fp32 values (exact: pow2 * pow2)."""
        return self.in_scale * self.w_scale


def normalize_pool(pool: int, pool_stride: int | None = None) -> tuple:
    """Normalize the (pool, pool_stride) sugar into a concrete
    ``(window, stride)`` pair; ``(0, 0)`` means pooling disabled.

    ``pool`` is the square window size (0 disables, the historic ``pool=2``
    means 2x2); ``pool_stride=None`` defaults to the window (the
    window == stride case every paper topology uses).
    """
    if pool is None:
        pool = 0
    if not isinstance(pool, int) or isinstance(pool, bool):
        raise ValueError(f"pool must be an int window size, got {pool!r}")
    if pool < 0:
        raise ValueError(f"pool window must be >= 0 (0 = no pool), got {pool}")
    if pool == 0:
        if pool_stride not in (None, 0):
            raise ValueError(
                f"pool_stride={pool_stride!r} given but pooling is disabled "
                "(pool=0)"
            )
        return (0, 0)
    ps = pool if pool_stride is None else pool_stride
    if not isinstance(ps, int) or isinstance(ps, bool) or ps < 1:
        raise ValueError(
            f"pool_stride must be a positive int (or None = window), got "
            f"{pool_stride!r}"
        )
    return (pool, ps)


def pool_out_dim(d: int, window: int, stride: int) -> int:
    """VALID sliding-window output length for one spatial dim."""
    return (d - window) // stride + 1


def stream_quant_spec(act_bits: int) -> FixedPointSpec:
    """The feature-stream Q-format: 1 sign bit, 1 integer bit, rest
    fractional — the same format the model-level fake-quant reference
    applies to activations."""
    return FixedPointSpec(bits=act_bits, frac_bits=act_bits - 2)


def validate_epilogue(
    act: str,
    pool: int,
    pool_stride: int | None = None,
    act_bits: int | None = None,
) -> None:
    if act not in ACTS:
        raise ValueError(f"unknown act {act!r}; expected one of {ACTS}")
    normalize_pool(pool, pool_stride)
    if act_bits is not None and act_bits < 2:
        raise ValueError(f"act_bits must be >= 2 (or None), got {act_bits}")


def strided_view(x, axis: int, start: int, size: int, stride: int):
    """``size`` elements of ``x`` along ``axis`` from ``start``, every
    ``stride``-th — ``x[start : start + (size - 1) * stride + 1 : stride]``.

    Written as a unit-stride slice, a split reshape ``(size, stride)`` and
    an index, because Mosaic lowers neither strided value slices nor
    gathers but does lower these three. Rows past the end are zero padding
    that the index never selects, so the values are exactly the strided
    slice's. Mosaic cannot split a packed int8 dim, so int8 moves as int32
    (an exact round trip)."""
    axis %= x.ndim
    if stride == 1:
        return jax.lax.slice_in_dim(x, start, start + size, axis=axis)
    end = start + size * stride
    if end > x.shape[axis]:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, end - x.shape[axis])
        x = jnp.pad(x, pad)
    dtype = x.dtype
    if dtype == jnp.int8:
        x = x.astype(jnp.int32)
    x = jax.lax.slice_in_dim(x, start, end, axis=axis)
    x = x.reshape(x.shape[:axis] + (size, stride) + x.shape[axis + 1 :])
    return jax.lax.index_in_dim(x, 0, axis + 1, keepdims=False).astype(dtype)


def _maxpool_window(y, window: int, stride: int):
    """Square max-pool over the trailing (H, W, N) dims of ``y`` via
    window*window shifted strided views — plain jnp ops (elementwise max +
    :func:`strided_view`), so it runs unchanged inside a Pallas kernel
    body on a VMEM-resident block."""
    *_, h, w, _ = y.shape
    hp = pool_out_dim(h, window, stride)
    wp = pool_out_dim(w, window, stride)
    out = None
    for di in range(window):
        rows = strided_view(y, -3, di, hp, stride)
        for dj in range(window):
            v = strided_view(rows, -2, dj, wp, stride)
            out = v if out is None else jnp.maximum(out, v)
    return out


def quantize_stream(x, act_bits: int):
    """Quantize fp32 values onto the ``act_bits`` stream grid as int8
    CODES (value = code * scale). Exact (a pure representation change)
    when ``x`` already sits on the grid — which every fused-kernel
    boundary guarantees. int8 holds any stream code: ``act_bits <= 8``
    is enforced by the compile-time ``int8_compute`` validation."""
    spec = stream_quant_spec(act_bits)
    q = jnp.clip(jnp.round(x / spec.scale), spec.qmin, spec.qmax)
    return q.astype(jnp.int8)


def pool_pad_mask(y, row0, col0, rows: int, cols: int):
    """``y`` (..., R, W, N) with every position outside the conv map
    ``[0, rows) x [0, cols)`` set to -inf: the padding a padded max-pool
    reads. ``row0``/``col0`` are the map coordinates of ``y[..., 0, 0, :]``
    (traced scalars inside a kernel)."""
    r = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 3) + row0
    c = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 2) + col0
    inside = (r >= 0) & (r < rows) & (c >= 0) & (c < cols)
    return jnp.where(inside, y, -jnp.inf)


def apply_epilogue(
    y, bias, *, act: str, pool: int, pool_stride: int | None = None,
    act_bits: int | None = None, ste: bool = False, pool_first: bool = False,
    codes_out: bool = False, residual=None, pool_pad=None,
):
    """y: (..., H, W, N) f32; bias: (N,). Returns the block after
    bias (+ ``residual``, a residual join's shortcut, added before
    anything else) + activation + optional pool x pool / pool_stride
    max-pool (VALID floor semantics) + optional feature-stream
    quantization — all in-register/VMEM.

    ``pool_pad`` ``(row0, col0, rows, cols)`` marks the block's place in
    the conv map (see :func:`pool_pad_mask`): positions outside it are
    -inf when pooled, so a block computed over a padded frame pools
    exactly as a -inf-padded max-pool.

    ``ste=True`` routes the quantization through ``fake_quant_ste``
    (identity gradient inside the representable range) — same forward
    values, used by the differentiable XLA rendering so QAT through the
    fused path keeps training. The Pallas kernel body keeps the raw
    round/clip (``ste=False``): it is forward-only anyway, and the kernel
    program must stay plain jnp ops.

    ``pool_first=True`` swaps the act/pool actors: bias -> max-pool ->
    activation -> quant, which is the composition order of
    ``cnn_apply_reference``. Because max-pool commutes with the monotone
    activations the two orders agree; pooling first shrinks the
    activation work by the pool factor, so the cross-layer fused pyramid
    uses it (the single-layer actor chain keeps the paper's
    conv -> act -> pool order).

    ``codes_out=True`` (true-int8 pyramid interiors) returns the stream
    quantization's int8 CODES instead of the dequantized fp32 values —
    the inter-layer slab stays 1 byte/element in VMEM and the next
    layer's integer matmul consumes it directly. Requires ``act_bits``
    and is mutually exclusive with ``ste`` (the codes path is
    forward-only).
    """
    validate_epilogue(act, pool, pool_stride, act_bits)
    if codes_out and (act_bits is None or ste):
        raise ValueError(
            "codes_out requires act_bits and is forward-only (ste=False)"
        )
    pw, ps = normalize_pool(pool, pool_stride)
    y = y + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual

    def pool_y(v):
        if pool_pad is not None:
            v = pool_pad_mask(v, *pool_pad)
        return _maxpool_window(v, pw, ps)

    if pool_first and pw:
        y = pool_y(y)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "tanh":
        y = jnp.tanh(y)
    if not pool_first and pw:
        y = pool_y(y)
    if act_bits is not None:
        spec = stream_quant_spec(act_bits)
        if ste:
            from repro.core.quant.fixed_point import fake_quant_ste

            y = fake_quant_ste(y, spec)
        else:
            q = jnp.clip(jnp.round(y / spec.scale), spec.qmin, spec.qmax)
            y = q.astype(jnp.int8) if codes_out else q * spec.scale
    return y
