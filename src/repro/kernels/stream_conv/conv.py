"""Pallas TPU kernel: row/column-blocked streaming convolution with a fused
conv -> bias -> activation -> pool epilogue (paper [10], §5).

The FPGA conv engine of the paper chains three always-firing actors —
convolution, activation, pooling — with no intermediate frame storage. The
TPU rendering streams the image through the grid in **row x column blocks**
and runs the whole actor chain on each block before anything is written
back:

  grid = (B, H'/R, W'/WC, N/bn, C/bc): one (R x WC)-output tile per
  (batch, row-block, col-block, feature-block) cell, accumulated over
  channel blocks. Each step

    1. receives its input tile through the BlockSpec pipeline: an
       (R*s x WC*s) body block plus a halo strip on the bottom/right edge
       (and the corner); with a single column block the body spans the
       whole padded width and only the bottom strip remains — the halo
       is the line buffer: the only pixels ever fetched twice. The halo
       width ``hb = max(0, (P_w - P_s)*s +
       K - s)`` covers both the conv window overlap (K - s) and the pool
       window overlap ((P_w - P_s) conv rows re-computed so overlapping
       pool windows never straddle a block boundary),
    2. assembles the K*K stride-s shifted views into ONE
       (R'*WC', K*K*bc) operand and issues a SINGLE MXU matmul against the
       flattened (K*K*bc, bn) tap matrix — the fully-unrolled multiplier
       array of Fig. 1-c collapsed into one systolic pass, not K*K
       per-tap dots,
    3. on the last channel block, applies the fused epilogue in VMEM:
       + bias, activation (relu/tanh), P_w x P_w / stride-P_s max-pool —
       conv, activation and pooling actors as one hardware pipeline stage,
    4. writes back only the pooled tile: HBM traffic is one read of x
       (plus the halo strips), zero intermediate conv/activation frames,
       and a pool-factor-smaller output.

Weights are expected as (K*K, C, N) — taps flattened, channels C and
features N as the hardware-aligned dims. VALID padding, conv stride ``s``
(SAME is padded by the host wrapper, as the FPGA engine pads the pixel
stream at frame edges). Channel blocks (``block_c``) and feature blocks
(``block_n``) bound the VMEM working set so CIFAR/SVHN-sized layers fit;
row blocks (``block_r``) amortize grid overhead and feed the MXU tall
operands; column blocks (``block_w``) let frames wider than VMEM lower
(0 = whole width per block, the single-column-block fast path).

Block-size legality: the conv-output rows per block R must be a multiple
of lcm(pool stride, hb / gcd(hb, s)) so (a) pooled rows tile exactly and
(b) the halo BlockSpec's element offset (rb+1)*R*s is expressible in
halo-block units. Same rule for WC along the width. The wrapper rounds the
requested block_r/block_w up to the nearest legal size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import pad_axis_to, round_up
from repro.kernels.stream_conv.epilogue import (
    apply_epilogue,
    normalize_pool,
    pool_out_dim,
    strided_view,
    validate_epilogue,
)
from repro.kernels.stream_conv.halo import BLOCK_SPAN, group_geometry

# Scoped VMEM the residual and single-layer kernels may use. A v5e
# TensorCore has 128 MiB of VMEM; Mosaic's default scope is 16 MiB, which
# the fusion planner's budget (a sum of slabs, not of Mosaic's tiled
# buffers) is set against. Tiled, a 56x56x64 block or a row block of a
# 224-px 3-channel frame (3 of 128 lanes used) runs past that default.
# The chain pyramid keeps the default scope, and so its compiled form.
WIDE_VMEM = pltpu.CompilerParams(vmem_limit_bytes=96 * 1024 * 1024)


def _kernel_body(
    x_blk, w_ref, b_ref, o_ref, acc_ref, *, k, s, r_conv, w_conv, act,
    pool, pool_stride, act_bits, int8_scales, out_dtype, pool_pad=None,
):
    """Shared body: x_blk is the assembled ((r_conv-1)*s + k,
    (w_conv-1)*s + k, bc) input tile (body + halo strips).

    With ``int8_scales`` the tile and taps are int8 codes: the single
    matmul contracts integers into the int32 accumulator scratch and the
    write-back epilogue dequantizes with one exact pow2 multiply before
    requantizing onto the ``act_bits`` stream grid — true integer MXU
    arithmetic, same epilogue contract."""
    cb = pl.program_id(4)
    n_cb = pl.num_programs(4)
    origin = None
    if pool_pad is not None:
        # The block's first row and column in the conv map: the frame was
        # shifted by pp conv rows and columns, which pool as -inf.
        pp, rows, cols, r, wc = pool_pad
        origin = (
            pl.program_id(1) * r - pp, pl.program_id(2) * wc - pp, rows, cols,
        )

    @pl.when(cb == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bc = x_blk.shape[-1]
    # K*K stride-s shifted views of the tile -> one tall operand. Pure data
    # movement (VPU); the contraction below is the only matmul.
    operand = _assemble_taps(x_blk, k, s, r_conv, w_conv)
    if int8_scales is not None:
        w_flat = _merge_rows(w_ref[...])
        # ONE integer MXU matmul per tile, int32 accumulation.
        acc_ref[...] += jnp.dot(
            operand, w_flat, preferred_element_type=jnp.int32
        ).reshape(r_conv, w_conv, -1)
    else:
        operand = operand.astype(jnp.float32)
        w_flat = w_ref[...].reshape(k * k * bc, -1).astype(jnp.float32)
        # ONE MXU matmul per tile (per channel-block accumulation step).
        acc_ref[...] += jnp.dot(
            operand, w_flat, preferred_element_type=jnp.float32
        ).reshape(r_conv, w_conv, -1)

    @pl.when(cb == n_cb - 1)
    def _write():
        y = acc_ref[...]
        if int8_scales is not None:
            y = y.astype(jnp.float32) * int8_scales.deq_scale
        y = apply_epilogue(
            y, b_ref[...], act=act, pool=pool,
            pool_stride=pool_stride, act_bits=act_bits, pool_pad=origin,
        )
        o_ref[0] = y.astype(out_dtype)


def _fused_kernel(*refs, n_tiles, **kw):
    """refs = the input tiles (body; + bottom halo; + right halo and
    corner), then w_ref, b_ref, o_ref, acc_ref. Assembles the tiles into
    one block and runs the shared body."""
    t = [ref[0] for ref in refs[:n_tiles]]
    if n_tiles == 4:
        t = [
            jnp.concatenate([t[0], t[2]], axis=1),
            jnp.concatenate([t[1], t[3]], axis=1),
        ]
    x_blk = t[0] if len(t) == 1 else jnp.concatenate(t, axis=0)
    _kernel_body(x_blk, *refs[n_tiles:], **kw)


def _block_multiple(k: int, s: int, pw: int, ps: int) -> tuple:
    """(legal block multiple, halo pixels, pool-overlap conv rows) for one
    spatial dim. The block multiple is lcm(pool stride, hb/gcd(hb, s)):
    pooled outputs must tile blocks exactly, and the halo BlockSpec offset
    (idx+1)*R*s must land on a halo-block boundary."""
    overlap = max(0, pw - ps) if pw else 0
    hb = max(0, overlap * s + k - s)
    mult = 1
    if pw:
        mult = math.lcm(mult, ps)
    if hb:
        mult = math.lcm(mult, hb // math.gcd(hb, s))
    return mult, hb, overlap


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "stride", "act", "pool", "pool_stride", "pool_pad", "act_bits",
        "int8_scales", "block_r", "block_w", "block_c", "block_n",
        "out_dtype", "interpret",
    ),
)
def stream_conv_fused_pallas(
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
    block_r: int = 8,
    block_w: int = 0,  # 0 = full conv-output width per block
    block_c: int = 0,  # 0 = full C per step
    block_n: int = 0,  # 0 = full N per step
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Fused streaming conv. VALID, conv stride ``stride``; ``pool`` is a
    square max-pool window (0 = none) sliding with ``pool_stride``
    (default: the window), padded by ``pool_pad`` -inf rows and columns
    per side; act in {none, relu, tanh}; ``act_bits`` quantizes the output
    feature stream in-kernel. Returns (B, H', W', N) where H', W' are the
    pooled output dims.

    A padded pool computes ``pool_pad`` extra conv rows and columns on
    each side of the map (the frame is shifted by ``pool_pad * stride``
    zero pixels) and masks them to -inf before pooling, so every block
    pools exactly as the padded pool.

    ``int8_scales`` (``epilogue.Int8Scales``) selects the true-int8
    rendering: ``x`` must arrive pre-quantized as int8 stream codes (the
    host wrapper quantizes OUTSIDE the pallas_call so the resident frame
    is 1 byte/element) and ``w_taps`` as int8 weight codes; the kernel
    contracts integers into an int32 accumulator scratch and dequantizes
    at write-back."""
    b, h, wd, c = x.shape
    kk, c2, n = w_taps.shape
    if int8_scales is not None:
        if x.dtype != jnp.int8 or w_taps.dtype != jnp.int8:
            raise ValueError(
                "int8_scales requires int8 code operands, got "
                f"x={x.dtype}, w_taps={w_taps.dtype}"
            )
    if kk != k * k or c2 != c:
        raise ValueError(f"w_taps {w_taps.shape} inconsistent with k={k}, C={c}")
    if bias.shape != (n,):
        raise ValueError(f"bias must be ({n},), got {bias.shape}")
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
    pad_origin = None
    if pool_pad:
        if not pw or 2 * pool_pad > pw:
            raise ValueError(
                f"pool_pad={pool_pad} needs a pool window of at least "
                f"{2 * pool_pad}, got {pw}"
            )
        shift = pool_pad * s
        x = jnp.pad(x, ((0, 0), (shift, 0), (shift, 0), (0, 0)))
        pad_origin = (pool_pad, h_out, w_out)
        h_out, w_out = h_out + 2 * pool_pad, w_out + 2 * pool_pad

    mult, hb, overlap = _block_multiple(k, s, pw, ps)
    r = round_up(max(block_r, mult), mult)
    r = min(r, round_up(h_out, mult))
    wc = block_w if block_w > 0 else w_out
    wc = round_up(max(wc, mult), mult)
    wc = min(wc, round_up(w_out, mult))
    # Conv rows/cols actually computed per block: the pool-window overlap
    # rows are re-computed from the halo so overlapping pool windows never
    # cross a block boundary.
    r_conv, w_conv = r + overlap, wc + overlap

    r_o = r // ps if pw else r
    wc_o = wc // ps if pw else wc
    h_keep = pool_out_dim(h_out, pw, ps) if pw else h_out
    w_keep = pool_out_dim(w_out, pw, ps) if pw else w_out
    n_rb = -(-h_keep // r_o)
    n_wb = -(-w_keep // wc_o)

    bc = min(block_c, c) if block_c > 0 else c
    bn = min(block_n, n) if block_n > 0 else n
    c_pad = round_up(c, bc)
    n_pad = round_up(n, bn)

    # Host-side zero padding: rows/cols so every body+halo block is in
    # bounds (pad pixels only feed discarded outputs: kept pool windows
    # read only conv outputs < h_out/w_out, which read only real pixels),
    # channels/features so the block grid divides evenly (zero channels
    # contribute zero partials).
    xp = pad_axis_to(x, 1, n_rb * r * s + hb)
    xp = pad_axis_to(xp, 2, n_wb * wc * s + hb)
    xp = pad_axis_to(xp, 3, c_pad)
    wp = pad_axis_to(pad_axis_to(w_taps, 1, c_pad), 2, n_pad)
    bp = pad_axis_to(bias, 0, n_pad)

    grid = (b, n_rb, n_wb, n_pad // bn, c_pad // bc)
    kw = dict(
        k=k, s=s, r_conv=r_conv, w_conv=w_conv, act=act, pool=pool,
        pool_stride=pool_stride, act_bits=act_bits,
        int8_scales=int8_scales, out_dtype=out_dtype,
    )
    if pad_origin is not None:
        kw["pool_pad"] = (*pad_origin, r, wc)

    # With one column block the body tile spans the whole padded width, so
    # only a bottom halo strip remains and every tile's trailing dims equal
    # the array's (Mosaic's block-shape rule); column blocking adds the
    # right strip and the corner.
    col_w = wc * s + hb if n_wb == 1 else wc * s
    in_specs = [
        pl.BlockSpec(
            (1, r * s, col_w, bc),
            lambda bb, rb, wb, nb, cb: (bb, rb, wb, cb),
        ),
    ]
    if hb:
        # Halo strips: bottom rows (and, with column blocks, right cols and
        # the corner). Element offset (idx+1)*R*s expressed in hb-sized
        # block units (legal by the block-multiple rule above).
        rs_hb = r * s // hb
        ws_hb = wc * s // hb
        in_specs.append(
            pl.BlockSpec(
                (1, hb, col_w, bc),
                lambda bb, rb, wb, nb, cb: (bb, (rb + 1) * rs_hb, wb, cb),
            )
        )
        if n_wb > 1:
            in_specs += [
                pl.BlockSpec(
                    (1, r * s, hb, bc),
                    lambda bb, rb, wb, nb, cb: (bb, rb, (wb + 1) * ws_hb, cb),
                ),
                pl.BlockSpec(
                    (1, hb, hb, bc),
                    lambda bb, rb, wb, nb, cb: (
                        bb, (rb + 1) * rs_hb, (wb + 1) * ws_hb, cb
                    ),
                ),
            ]
    n_tiles = len(in_specs)
    inputs = [xp] * n_tiles
    kernel = functools.partial(_fused_kernel, n_tiles=n_tiles, **kw)
    in_specs += [
        pl.BlockSpec((k * k, bc, bn), lambda bb, rb, wb, nb, cb: (0, cb, nb)),
        pl.BlockSpec((bn,), lambda bb, rb, wb, nb, cb: (nb,)),
    ]
    inputs += [wp, bp]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, r_o, wc_o, bn), lambda bb, rb, wb, nb, cb: (bb, rb, wb, nb)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_rb * r_o, n_wb * wc_o, n_pad), out_dtype
        ),
        scratch_shapes=[
            pltpu.VMEM(
                (r_conv, w_conv, bn),
                jnp.int32 if int8_scales is not None else jnp.float32,
            )
        ],
        compiler_params=WIDE_VMEM,
        interpret=interpret,
    )(*inputs)
    return out[:, :h_keep, :w_keep, :n]


# ---------------------------------------------------------------------------
# Cross-layer fused pyramid: several conv->bias->act->pool layers per
# pallas_call, inter-layer slabs VMEM-resident.


def _assemble_taps(slab, k: int, s: int, conv_rows: int, conv_cols: int):
    """Two-step tap assembly: column shifts first (k strided views of the
    slab along its sublane dim), then row shifts of each (cheap views
    along the untiled leading dim), laid side by side on the lane dim in
    (ki, kj, C) order — the (rows*cols, k*k*C) matmul operand. Pure VPU
    data movement; the contraction stays ONE matmul per layer per block."""
    cols = [strided_view(slab, 1, kj, conv_cols, s) for kj in range(k)]
    taps = [
        strided_view(z, 0, ki, conv_rows, s) for ki in range(k) for z in cols
    ]
    return _merge_rows(jnp.concatenate(taps, axis=-1))


def _merge_rows(x):
    """(R, W, L) -> (R*W, L). Mosaic merges packed int8 rows only when W
    keeps whole 4-row groups; otherwise the merge runs on int32 (exact)."""
    r, w, lanes = x.shape
    if x.dtype == jnp.int8 and w % 4:
        return x.astype(jnp.int32).reshape(r * w, lanes).astype(jnp.int8)
    return x.reshape(r * w, lanes)


def _shortcut(src, col0: int, g, src_scale, proj_scales, proj_refs):
    """A residual join's shortcut for one block: every ``res_stride``-th
    row and column of the held block-input slab ``src`` (from row
    ``res_off``, column ``col0``), as fp32 values, or their 1x1
    projection (one more MXU dot, its own bias and int8 contract) for a
    ``project`` join. ``src_scale`` is the block input's stream step
    (None on the float path)."""
    st = g.res_stride
    v = strided_view(src, 0, g.res_off, g.conv_slab_rows, st)
    v = strided_view(v, 1, col0, g.conv_cols, st)
    if g.join == "identity":
        return v if src_scale is None else v.astype(jnp.float32) * src_scale
    w_ref, b_ref = proj_refs
    operand = _merge_rows(v)
    if proj_scales is not None:
        y = jnp.dot(operand, w_ref[...], preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * proj_scales.deq_scale
    else:
        y = jnp.dot(
            operand.astype(jnp.float32), w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    return y.reshape(g.conv_slab_rows, g.conv_cols, g.n_out) + b_ref[...]


def _pyramid_kernel(*refs, geom, act_bits, int8_scales, out_dtype):
    """Kernel body: stream one row block of the final output through the
    whole fusion group. refs = (x_ref, w_ref0, b_ref0, w_ref1, b_ref1, ...,
    then (w, b) of each ``project`` join in layer order, o_ref). Every
    inter-layer slab lives in VMEM for the block's lifetime; nothing is
    written back until the last layer's pooled rows.

    ``act_bits`` is a per-layer tuple; ``int8_scales`` (None or a
    per-layer tuple of ``Int8Scales``) selects true integer arithmetic:
    the resident frame and every inter-layer slab are int8 stream CODES
    (1 byte/element in VMEM — intermediate epilogues emit ``codes_out``),
    each layer's single matmul contracts integers into int32, and only
    the group's final epilogue dequantizes to fp32 grid values.

    A layer with a residual join adds its shortcut before its epilogue's
    activation. The shortcut reads the block's input slab, held in VMEM
    from the block's first layer (before its column padding), so the
    skip edge never leaves the chip."""
    x_ref, o_ref = refs[0], refs[-1]
    n_layers = len(geom.layers)
    wb = refs[1 : 1 + 2 * n_layers]
    proj = iter(zip(refs[1 + 2 * n_layers : -1 : 2],
                    refs[2 + 2 * n_layers : -1 : 2]))
    rb = pl.program_id(1)

    g0 = geom.layers[0]
    start0 = g0.in_mult * rb + g0.in_off + geom.input_row_shift
    slab = x_ref[0, pl.ds(start0, g0.in_slab_rows), :, :]
    if int8_scales is None:
        slab = slab.astype(jnp.float32)

    held = None  # (block-input slab, its first column, its stream step)
    for i, g in enumerate(geom.layers):
        sc = None if int8_scales is None else int8_scales[i]
        if i > 0:
            # The slab is the previous layer's output over an affine row
            # interval that may reach outside the frame: rows outside
            # [0, in_rows) are exactly this layer's SAME zero padding
            # (VALID layers never read them — they only feed rows that
            # are discarded downstream). Zero is dtype-preserving: on the
            # int8 path code 0 IS value 0.
            rows = (
                jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
                + g.in_mult * rb + g.in_off
            )
            slab = jnp.where(
                (rows >= 0) & (rows < g.in_rows), slab, jnp.zeros_like(slab)
            )
        closer = i + BLOCK_SPAN - 1
        if closer < n_layers and geom.layers[closer].join != "none":
            held = (
                slab,
                geom.in_pad_cols[0] if i == 0 else 0,
                None if sc is None else sc.in_scale,
            )
        if i > 0:
            lc, rc = g.pads[1]
            if lc or rc:
                slab = jnp.pad(slab, ((0, 0), (lc, rc), (0, 0)))
        operand = _assemble_taps(
            slab, g.k, g.stride, g.conv_slab_rows, g.conv_cols
        )
        w_flat = wb[2 * i][...]  # (k*k*C, N), flattened by the host
        if sc is not None:
            # ONE integer MXU matmul per layer per block -> int32 acc ->
            # exact pow2 dequant.
            y = jnp.dot(
                operand, w_flat, preferred_element_type=jnp.int32
            ).reshape(g.conv_slab_rows, g.conv_cols, g.n_out)
            y = y.astype(jnp.float32) * sc.deq_scale
        else:
            # ONE MXU matmul per layer per block.
            y = jnp.dot(
                operand,
                w_flat.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).reshape(g.conv_slab_rows, g.conv_cols, g.n_out)
        residual = None
        if g.join != "none":
            src, col0, src_scale = held
            residual = _shortcut(
                src, col0, g, src_scale, None if sc is None else sc.proj,
                next(proj) if g.join == "project" else None,
            )
        slab = apply_epilogue(
            y, wb[2 * i + 1][...], act=g.act, pool=g.pw,
            pool_stride=g.ps, act_bits=act_bits[i], pool_first=True,
            codes_out=sc is not None and i < n_layers - 1,
            residual=residual,
        )
    o_ref[0] = slab.astype(out_dtype)


def _pyramid_call(
    x, weights, biases, projections, *, layers, act_bits, int8_scales,
    block_rows, out_dtype, interpret, compiler_params=None,
):
    """The pallas_call of a fusion group (see
    :func:`stream_conv_pyramid_pallas`); ``projections`` holds the (w, b)
    of each ``project`` join, in layer order."""
    b, h, w, c = x.shape
    if int8_scales is not None and x.dtype != jnp.int8:
        raise ValueError(
            f"int8_scales requires a pre-quantized int8 frame, got {x.dtype}"
        )
    bits = (
        act_bits
        if isinstance(act_bits, tuple)
        else (act_bits,) * len(layers)
    )
    kernels = tuple(wt.shape[0] for wt in weights)
    n_outs = tuple(wt.shape[3] for wt in weights)
    geom = group_geometry(
        h, w, c, layers, kernels, n_outs, block_rows=block_rows
    )
    lc, rc = geom.in_pad_cols
    xp = jnp.pad(
        x,
        (
            (0, 0),
            (geom.in_pad_top, geom.in_pad_rows_total - h - geom.in_pad_top),
            (lc, rc),
            (0, 0),
        ),
    )
    rows_tot, cols_tot = xp.shape[1], xp.shape[2]

    grid = (b, geom.n_row_blocks)
    in_specs = [
        pl.BlockSpec((1, rows_tot, cols_tot, c), lambda bb, rb: (bb, 0, 0, 0))
    ]
    inputs = [xp]
    for g, wt, bs in zip(geom.layers, weights, biases):
        # Taps flattened on the host: the (k*k*C, N) matmul operand is
        # what the kernel reads, with no in-kernel reshape of packed int8.
        kkc = g.k * g.k * g.in_ch
        in_specs.append(pl.BlockSpec((kkc, g.n_out), lambda bb, rb: (0, 0)))
        in_specs.append(pl.BlockSpec((g.n_out,), lambda bb, rb: (0,)))
        inputs += [wt.reshape(kkc, g.n_out), bs]
    for wt, bs in projections:
        ci, n = wt.shape[2], wt.shape[3]
        in_specs.append(pl.BlockSpec((ci, n), lambda bb, rb: (0, 0)))
        in_specs.append(pl.BlockSpec((n,), lambda bb, rb: (0,)))
        inputs += [wt.reshape(ci, n), bs]

    n_last = n_outs[-1]
    out = pl.pallas_call(
        functools.partial(
            _pyramid_kernel, geom=geom, act_bits=bits,
            int8_scales=int8_scales, out_dtype=out_dtype,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, geom.block_rows, geom.out_cols, n_last),
            lambda bb, rb: (bb, rb, 0, 0),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, geom.n_row_blocks * geom.block_rows, geom.out_cols, n_last),
            out_dtype,
        ),
        compiler_params=compiler_params,
        interpret=interpret,
    )(*inputs)
    return out[:, : geom.out_rows]


@functools.partial(
    jax.jit,
    static_argnames=(
        "layers", "act_bits", "int8_scales", "block_rows", "out_dtype",
        "interpret",
    ),
)
def stream_conv_pyramid_pallas(
    x: jax.Array,  # (B, H, W, C0), unpadded
    weights: tuple,  # per layer (K, K, C, N) HWIO
    biases: tuple,  # per layer (N,)
    *,
    layers: tuple,  # PyramidLayer per layer
    act_bits=None,  # int | None | per-layer tuple
    int8_scales=None,  # None | per-layer tuple of Int8Scales
    block_rows: int = 0,  # final-output rows per block; 0 = whole frame
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Cross-layer fused conv pyramid: the whole group is ONE pallas_call.

    Grid = (B, n_row_blocks); each cell streams one block of the *final*
    output rows through every layer of the group — conv (one matmul per
    layer), bias, pool, act, stream quant — with all inter-layer feature
    slabs VMEM-resident. The block's input halo is the composed per-layer
    requirement (``halo.group_geometry``); SAME padding of intermediate
    layers is realized by masking slab rows outside the valid frame, which
    is exactly the zero padding those rows carry. Returns the group output
    (B, H', W', N_last).
    """
    return _pyramid_call(
        x, weights, biases, (), layers=layers, act_bits=act_bits,
        int8_scales=int8_scales, block_rows=block_rows, out_dtype=out_dtype,
        interpret=interpret,
    )




RESIDUAL_KERNEL = "stream_conv_residual_pallas"


@functools.lru_cache(maxsize=None)
def residual_kernel(first: int, last: int):
    """The pyramid kernel of a fusion group that carries residual joins,
    computing the plan's conv layers ``first``..``last``. It is jitted
    under the name ``stream_conv_residual_pallas_l<first>_<last>``, which
    the device trace shows as the kernel's event name
    (``%<name>.<n> = ...``), so a trace reader can price every call.

    Same kernel body as :func:`stream_conv_pyramid_pallas`: each basic
    block's input slab stays in VMEM from its first layer to the join,
    where the shortcut (or its 1x1 projection) is added before the
    activation. Called as ``(x, weights, biases, projections, *, layers,
    act_bits, int8_scales, block_rows, out_dtype, interpret)``, with
    ``projections`` the (w (1, 1, C, N), b (N,)) of each ``project``
    join in layer order."""

    def kernel(
        x, weights, biases, projections, *, layers, act_bits=None,
        int8_scales=None, block_rows=0, out_dtype=jnp.float32,
        interpret=False,
    ):
        return _pyramid_call(
            x, weights, biases, projections, layers=layers,
            act_bits=act_bits, int8_scales=int8_scales,
            block_rows=block_rows, out_dtype=out_dtype, interpret=interpret,
            compiler_params=WIDE_VMEM,
        )

    kernel.__name__ = kernel.__qualname__ = f"{RESIDUAL_KERNEL}_l{first}_{last}"
    return jax.jit(
        kernel,
        static_argnames=(
            "layers", "act_bits", "int8_scales", "block_rows", "out_dtype",
            "interpret",
        ),
    )
