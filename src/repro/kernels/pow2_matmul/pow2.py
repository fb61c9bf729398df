"""Pallas TPU kernel: weight-only pow2-codebook quantized matmul.

The TPU-native rendering of the paper's constant-specialized multipliers:

- Weights live in HBM as **4-bit codes, two per byte** — 4x less weight
  bandwidth than bf16, 8x less than f32. On the bandwidth-bound decode path
  this is the direct analogue of the paper's multiplier-area reduction.
- In-kernel decode is **multiplication-free**: a code (sign s, magnitude m)
  becomes the float 2^(m-1) by *integer exponent construction*
  (``(126 + m) << 23`` bitcast to f32) — i.e. a shift, exactly like the
  paper's shift-register multipliers. Zero codes (m=0) decode to +0.0, the
  "multiplication removed" case.
- The per-output-channel scale is folded **after** the K-reduction: one
  multiply per output element instead of one per weight (the paper folds it
  into the activation's fixed-point alignment).

Grid: (M/bm, N/bn, K/bk), K innermost; accumulation in an f32 VMEM scratch,
written out (scaled) on the last K step. Block shapes default to
128x256x128, so the packed weight block (bk, bn//2) uint8 spans a whole
128-lane tile. Inside a block the kernel holds the even columns first and
the odd ones second (see ``_unpack_u4``); the wrapper undoes that order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_codes_f32(codes: jax.Array) -> jax.Array:
    """4-bit (sign|mag) codes -> f32 via exponent construction (no mults).

    value = (-1)^s * 2^(m-1) for m in [1..7]; m == 0 -> +0.0.
    IEEE754: exponent_field = 127 + (m - 1) = 126 + m.
    """
    c = codes.astype(jnp.int32)
    m = jnp.bitwise_and(c, 0x7)
    s = jnp.bitwise_and(c, 0x8)
    bits = jnp.left_shift(126 + m, 23) | jnp.left_shift(s, 28)  # s<<3 -> bit31
    bits = jnp.where(m == 0, 0, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _unpack_u4(packed: jax.Array) -> jax.Array:
    """(bk, bn//2) uint8 -> (bk, bn) int32 codes, block-permuted: the low
    nibbles (even columns) fill the first half and the high nibbles (odd
    columns) the second. Side by side is a lane concat, which Mosaic
    lowers; interleaving them back would take a lane shuffle, so the
    wrapper permutes the scale and the output columns instead."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate(
        [jnp.bitwise_and(p, 0x0F), jnp.right_shift(p, 4)], axis=-1
    )


def _halves_first(a: jax.Array, bn: int) -> jax.Array:
    """Reorder the last axis within each bn block: even columns, then odd
    (the kernel's unpacked column order)."""
    lead = a.shape[:-1]
    a = a.reshape(lead + (-1, bn // 2, 2))
    return jnp.swapaxes(a, -1, -2).reshape(lead + (-1,))


def _interleave_halves(a: jax.Array, bn: int) -> jax.Array:
    """Inverse of :func:`_halves_first`."""
    lead = a.shape[:-1]
    a = a.reshape(lead + (-1, 2, bn // 2))
    return jnp.swapaxes(a, -1, -2).reshape(lead + (-1,))


def _pow2_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, out_dtype):
    k_step = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(k_step == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_u4(w_ref[...])
    w = _decode_codes_f32(codes)  # (bk, bn) f32, unit scale
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(k_step == n_k - 1)
    def _write_out():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)).astype(
            out_dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def pow2_matmul_pallas(
    x: jax.Array,  # (M, K)
    packed: jax.Array,  # (K, N//2) uint8
    scale: jax.Array,  # (N,) f32
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n_half = packed.shape
    n = n_half * 2
    if k2 != k:
        raise ValueError(f"K mismatch: x {x.shape} vs packed {packed.shape}")
    if scale.shape != (n,):
        raise ValueError(f"scale must be ({n},), got {scale.shape}")
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"shape ({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn}); "
            "pad in ops.pow2_matmul"
        )
    if bn % 2:
        raise ValueError("block_n must be even (codes pack 2/byte)")

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_pow2_matmul_kernel, out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn // 2), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, _halves_first(scale, bn).reshape(1, n))
    return _interleave_halves(out, bn)
