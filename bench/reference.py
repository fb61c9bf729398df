"""The plain reference: the configuration's forward pass in straightforward
``jax.numpy`` and float32, computed under ``highest`` matmul precision.

The forward is the family's (``families/<name>.py``, ``forward``); this
module runs it in blocks and holds the quantization every family shares.
It imports nothing of the program and takes nothing the program made: the
weights come from the family's ``make_params`` and the quantization below
follows the paper's Q-format (arXiv:1712.04322, Sec. 4.1) as the
configuration file states it:

- ``fake_quant_bits`` (int8 configuration): every weight and bias tensor on
  its own power-of-two grid of that width (``2**(m - (b - 1))``, with
  ``2**m`` the smallest power of two at or above the tensor's largest
  magnitude), and the input frame and every conv and hidden activation on
  the stream grid ``Q(b, b - 2)`` (step ``2**-(b - 2)``). Round half to even,
  clip to the signed ``b``-bit range.
- ``dot_operands: "bfloat16"`` (float32 configuration at default matmul
  precision): the operands of every conv and dense product are rounded to
  bfloat16 and the products summed in float32, which is what one MXU pass
  at default precision computes on a TPU.
- ``dtype: "bfloat16"`` computes every value in bfloat16; it exists for the
  control (see ``check.py``), never for a configuration.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512  # most frames per reference call; the last block is zero-padded


def _pow2_fake_quant(x, bits: int):
    """Fake-quantize one tensor on its own power-of-two grid."""
    max_abs = jnp.max(jnp.abs(x))
    mant, exp = jnp.frexp(jnp.maximum(max_abs, 1e-12).astype(jnp.float32))
    m = jnp.where(mant == 0.5, exp - 1, exp)  # 2**(m-1) < max_abs <= 2**m
    scale = jnp.exp2((m - (bits - 1)).astype(jnp.float32)).astype(x.dtype)
    q = jnp.clip(jnp.round(x / scale), -(2 ** (bits - 1)), 2 ** (bits - 1) - 1)
    return q * scale


def _grid(x, bits: int):
    """Quantize activations onto the stream grid Q(bits, bits - 2)."""
    scale = 2.0 ** -(bits - 2)
    q = jnp.clip(jnp.round(x / scale), -(2 ** (bits - 1)), 2 ** (bits - 1) - 1)
    return q * scale


@functools.lru_cache(maxsize=None)
def _jitted(forward, topology_json: str, fake_quant_bits, dot_operands):
    topology = json.loads(topology_json)
    return jax.jit(lambda p, x: forward(
        p, x, topology, fake_quant_bits=fake_quant_bits, dot_operands=dot_operands
    ))


def logits(params, frames, forward, topology: dict, *, fake_quant_bits=None,
           dot_operands: str = "float32", dtype: str = "float32"):
    """Reference logits (float32 numpy, shape (N, classes)) of ``frames``
    (host array (N, H, W, C)) by the family's ``forward``, run in blocks
    of ``BLOCK`` frames so that it fits beside nothing else on the
    device."""
    dt = jnp.dtype(dtype)
    fwd = _jitted(forward, json.dumps(topology, sort_keys=True), fake_quant_bits,
                  dot_operands)
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dt), params)
    n = frames.shape[0]
    block = min(BLOCK, -(-n // 8) * 8)
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, n, block):
            blk = np.asarray(frames[s : s + block])
            pad = block - blk.shape[0]
            if pad:
                blk = np.concatenate([blk, np.zeros((pad,) + blk.shape[1:], blk.dtype)])
            y = fwd(p, jnp.asarray(blk, dt))
            out.append(np.asarray(y.astype(jnp.float32))[: block - pad])
    if not out:
        return np.zeros(jax.eval_shape(fwd, p, frames).shape, np.float32)
    return np.concatenate(out)
