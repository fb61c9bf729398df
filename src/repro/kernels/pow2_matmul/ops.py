"""Public jit'd wrapper for the pow2 matmul: quantization, padding to block
multiples, and dispatch by backend (see ``repro.kernels.backends``).

``backend="pallas"`` (the default) means compiled: Mosaic-compiled Pallas
on TPU; on platforms without compiled Pallas (XLA:CPU) it lowers the same
decode-then-matmul semantics through the XLA reference, so the default is
always a compiled path. ``pallas_interpret`` runs the kernel body through
the Pallas interpreter (the correctness oracle); ``ref`` forces the jnp
reference. Unknown backend strings raise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant.packing import pack_codes_u4
from repro.core.quant.pow2 import pow2_codes
from repro.kernels.backends import (
    DEFAULT_BACKEND,
    compiled_pallas_available,
    validate_backend,
)
from repro.kernels.padding import pad_axis_to, pad_axis_to_multiple
from repro.kernels.pow2_matmul.pow2 import pow2_matmul_pallas
from repro.kernels.pow2_matmul.ref import pow2_matmul_int_ref, pow2_matmul_ref


def quantize_weights(w: jax.Array):
    """(K, N) float weights -> (packed (K, ceil(N/2)) uint8, scale (N,) f32).

    Odd N is auto-padded with a zero column so two codes always fill a
    byte; zero codes decode to 0.0, so the pad is exact. The returned
    ``scale`` keeps the TRUE width N — it is the layer-width source of
    truth that lets ``pow2_matmul`` slice its output back to (M, N).
    """
    if w.ndim != 2:
        raise ValueError(f"expected (K, N) weights, got {w.shape}")
    n = w.shape[1]
    if n % 2:
        w = jnp.pad(w, ((0, 0), (0, 1)))
    codes, scale = pow2_codes(w, channel_axis=1)  # scale (1, N_even)
    return pack_codes_u4(codes), scale.reshape(-1)[:n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "out_dtype", "backend", "x_spec",
    ),
)
def pow2_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 128,
    out_dtype=jnp.float32,
    backend: str = DEFAULT_BACKEND,  # pallas | pallas_interpret | ref
    x_spec=None,  # FixedPointSpec of x's grid -> true-integer rendering
) -> jax.Array:
    """out[m, n] = sum_k x[m, k] * decode(codes[k, n]) * scale[n].

    The true layer width N is ``scale.shape[0]``; ``packed`` carries
    ceil(N/2) bytes (odd N is zero-column-padded by ``quantize_weights``).
    Shapes need not be block-aligned; inputs are zero-padded here (honoring
    the kernel's "pad in ops.pow2_matmul" contract — zero codes decode to
    0.0, so padding is exact) and the result is sliced back to (M, N).

    ``x_spec`` (a static ``FixedPointSpec``) switches XLA-rendered routes
    to the true-integer path: pow2 codes decode to int8 shift weights,
    activations quantize onto ``x_spec``'s grid, and one int8xint8->int32
    matmul replaces the decode-to-fp32 matmul (exact for on-grid x — both
    scales are pow2). The compiled TPU Pallas kernel keeps the fp32 decode
    for now (Mosaic-native shift-add is a roadmap item), so ``x_spec`` is
    honored on ref / CPU-fallback and ignored on compiled pallas.
    """
    validate_backend(backend)
    n = scale.shape[0]
    if packed.shape[1] != (n + 1) // 2:
        raise ValueError(
            f"packed width {packed.shape[1]} inconsistent with scale length "
            f"{n} (expected ceil(N/2) = {(n + 1) // 2} bytes)"
        )
    if backend == "ref" or (
        backend == "pallas" and not compiled_pallas_available()
    ):
        if x_spec is not None:
            return pow2_matmul_int_ref(
                x, packed, scale, x_spec=x_spec, out_dtype=out_dtype
            )
        return pow2_matmul_ref(x, packed, scale, out_dtype=out_dtype)
    m, k = x.shape
    n_even = packed.shape[1] * 2
    bm, bn, bk = min(block_m, m), min(block_n, n_even), min(block_k, k)
    bn = max(2, bn - (bn % 2))
    xp = pad_axis_to_multiple(pad_axis_to_multiple(x, 0, bm), 1, bk)
    wp = pad_axis_to_multiple(pad_axis_to_multiple(packed, 0, bk), 1, bn // 2)
    sp = pad_axis_to_multiple(pad_axis_to(scale, 0, n_even), 0, bn)
    out = pow2_matmul_pallas(
        xp,
        wp,
        sp,
        block_m=bm,
        block_n=bn,
        block_k=bk,
        out_dtype=out_dtype,
        interpret=(backend == "pallas_interpret"),
    )
    return out[:m, :n]
