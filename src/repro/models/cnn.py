"""CNN substrate in pure JAX: the paper's three benchmark networks plus
the non-paper generalization topologies.

Topologies (paper Table 1):

  LeNet5   : 28x28x1  -> conv(20,5) mpool tanh -> conv(50,5) mpool tanh -> FC
  Cifar10  : 32x32x3  -> conv(32,5) mpool tanh -> conv(32,5) mpool tanh
                       -> conv(64,5) mpool tanh -> FC
  SVHN     : same topology as Cifar10 (different learned kernel values).

LeNet5 uses VALID convolutions (Caffe's original LeNet), the CIFAR10/SVHN
topology uses SAME padding (Caffe's cifar10_quick), which reproduces the
paper's workload numbers exactly: 3.8 Mop (LeNet5 feature extractor) and
24.6 Mop (Cifar10/SVHN feature extractor).

Beyond the paper, ``CIFAR10_FULL`` (Caffe's cifar10_full: 5x5 SAME convs
with overlapping 3x3/stride-2 max-pool) and ``CIFAR10_STRIDED`` (stride-2
downsampling convs instead of pooling) exercise the generalized layer
vocabulary — conv ``stride``, ``(pool, pool_stride)`` windows with
window != stride, and rectangular frames — through the same DHM lowering
path as the paper nets. ``RESNET18`` (He et al., arXiv:1512.03385, Table 1,
"18-layer") adds residual joins, eval-mode batch norm, a padded max-pool
and global average pooling: the layer that closes a basic block (two
convs) carries the block's join, an identity shortcut or a 1x1/s
projection with its own batch norm.

Everything is functional: ``init_cnn`` builds a param pytree, ``cnn_apply``
runs the forward pass. ``cnn_apply`` is a thin veneer over the DHM
compiler: the topology + params + quantization spec lower through
``repro.core.dhm.compiler.compile_dhm`` into a plan of fused actor-chain
stages, and the forward pass runs that plan. ``conv_backend=None`` selects
the ``ref`` kernel backend (the lax.conv composition — the fast,
well-differentiable path for training); any ``repro.kernels.backends``
name routes the stages through the corresponding fused streaming kernel.
``cnn_apply_reference`` keeps the original hand-composed forward pass
(separate conv/bias/pool/act/fake-quant XLA ops) as the oracle compiled
plans are tested against. The two agree because pooling and the (monotone)
activations commute.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant.fixed_point import (
    FixedPointSpec,
    fake_quant_dynamic,
    fake_quant_ste,
)
from repro.kernels.stream_conv.halo import BLOCK_SPAN


BN_EPS = 1e-5  # eval-mode batch norm epsilon (He et al. / torchvision)


@dataclasses.dataclass(frozen=True)
class ConvLayerSpec:
    """One conv+mpool+act stage (a row of paper Table 1, generalized).

    ``pool`` is the square max-pool window (0 = no pool) and
    ``pool_stride`` its sliding stride; ``pool_stride=None`` means
    window == stride, so the historic ``pool=2`` sugar still reads as
    2x2/stride-2. ``pool_pad`` pads the pooled map on every side with
    -inf (ResNet's 3x3/2 pool, padding 1). ``stride`` is the conv stride.

    ``bn`` puts an eval-mode batch norm after the conv (its parameters
    ``gamma``, ``beta``, ``mean``, ``var`` ride in the layer's param
    dict). ``join`` closes a basic block of :data:`BLOCK_SPAN` convs: the
    block's input (the previous layer's input) is added to this layer's
    normalized conv output before pooling and the activation, either as
    it is (``identity``) or through a 1x1 projection conv with its own
    batch norm whose stride is the block's (``project``; its parameters
    ride under ``proj``).
    """

    n_out: int  # N: output feature maps
    kernel: int  # K
    padding: str = "VALID"  # VALID (LeNet5) or SAME (Cifar10/SVHN)
    pool: int = 2  # mpool window (0 = no pool)
    act: str = "tanh"
    stride: int = 1  # conv stride
    pool_stride: int | None = None  # None -> == pool (window == stride)
    pool_pad: int = 0  # -inf rows/cols around the pooled map, per side
    bn: bool = False  # eval-mode batch norm after the conv
    join: str = "none"  # halo.JOINS: "none" | "identity" | "project"

    @property
    def pool_cfg(self) -> tuple:
        """Concrete ``(window, stride)`` pool pair; ``(0, 0)`` = no pool.
        Only ``None`` defaults the stride to the window — an explicit
        invalid stride (e.g. 0) is kept so the compiler's validation
        rejects it instead of shape paths silently disagreeing."""
        if not self.pool:
            return (0, 0)
        ps = self.pool if self.pool_stride is None else self.pool_stride
        return (self.pool, ps)

    def out_hw(self, h: int, w: int) -> tuple:
        """(H, W) after this layer's conv + pool, from an (H, W) input."""
        h_c, w_c = self.conv_hw(h, w)
        pw, ps = self.pool_cfg
        if pw:
            p2 = 2 * self.pool_pad
            return (h_c + p2 - pw) // ps + 1, (w_c + p2 - pw) // ps + 1
        return h_c, w_c

    def conv_hw(self, h: int, w: int) -> tuple:
        """(H, W) after the conv alone (pre-pool)."""
        s = self.stride
        if self.padding == "SAME":
            return -(-h // s), -(-w // s)
        return (h - self.kernel) // s + 1, (w - self.kernel) // s + 1


@dataclasses.dataclass(frozen=True)
class CNNTopology:
    name: str
    input_hw: object  # int (square frame) or (H, W) tuple
    input_channels: int
    conv_layers: tuple
    fc_dims: tuple  # hidden FC dims of the classifier head
    n_classes: int
    global_pool: bool = False  # average the last map over H, W before FC

    def __post_init__(self):
        hw = self.input_hw
        ok = isinstance(hw, int) or (
            isinstance(hw, tuple) and len(hw) == 2
            and all(isinstance(d, int) for d in hw)
        )
        if not ok:
            raise ValueError(
                f"{self.name}: input_hw must be an int (square frame) or an "
                f"(H, W) tuple of ints, got {hw!r}"
            )

    @property
    def input_shape(self) -> tuple:
        """(H, W) of the input frame (int sugar means square)."""
        if isinstance(self.input_hw, int):
            return (self.input_hw, self.input_hw)
        return self.input_hw

    def square_input_hw(self) -> int:
        """The square frame side — raises clearly on rectangular inputs
        for the few paths (synthetic datasets) that still require
        squareness, instead of silently mis-shaping."""
        h, w = self.input_shape
        if h != w:
            raise ValueError(
                f"{self.name}: this path requires a square input frame, "
                f"got {h}x{w}"
            )
        return h

    def conv_shapes(self):
        """Per-layer (C_in, N_out, K, H_out, W_out) after conv (pre-pool)."""
        h, w = self.input_shape
        c = self.input_channels
        out = []
        for spec in self.conv_layers:
            h_conv, w_conv = spec.conv_hw(h, w)
            out.append((c, spec.n_out, spec.kernel, h_conv, w_conv))
            h, w = spec.out_hw(h, w)
            c = spec.n_out
        return out

    def projection_shapes(self) -> dict:
        """Per ``project`` join, keyed by the layer that carries it, the
        1x1 projection's (C_in, N_out, 1, H_out, W_out, stride): C_in is
        the block input's channels, the output is the layer's conv
        output and the stride is the block's."""
        shapes = self.conv_shapes()
        out = {}
        for i, spec in enumerate(self.conv_layers):
            if spec.join == "project":
                c_blk = shapes[i - BLOCK_SPAN + 1][0]
                _, n, _, h, w = shapes[i]
                s = 1
                for j in range(i - BLOCK_SPAN + 1, i + 1):
                    s *= self.conv_layers[j].stride
                out[i] = (c_blk, n, 1, h, w, s)
        return out

    def feature_shape(self) -> tuple:
        """(H, W, C) of the feature-extractor output (FC head input)."""
        h, w = self.input_shape
        c = self.input_channels
        for spec in self.conv_layers:
            h, w = spec.out_hw(h, w)
            c = spec.n_out
        return h, w, c

    def head_in_dim(self) -> int:
        """Width of the first FC layer's input: C after global average
        pooling, else the flattened feature map."""
        h, w, c = self.feature_shape()
        return c if self.global_pool else h * w * c

    def feature_extractor_macs(self) -> int:
        """MACs of the conv stack for one input frame, projections
        included."""
        convs = [sh[:5] for sh in self.projection_shapes().values()]
        return sum(
            c * n * k * k * h * w
            for (c, n, k, h, w) in self.conv_shapes() + convs
        )

    def feature_extractor_ops(self) -> int:
        """Ops (1 MAC = 2 ops) — the paper's 'Workload' column in Table 4."""
        return 2 * self.feature_extractor_macs()

    def n_multipliers(self) -> int:
        """Multipliers a full DHM instantiation needs: N*C*K*K per conv,
        projections included."""
        convs = [sh[:5] for sh in self.projection_shapes().values()]
        return sum(
            c * n * k * k for (c, n, k, _, _) in self.conv_shapes() + convs
        )


LENET5 = CNNTopology(
    name="lenet5",
    input_hw=28,
    input_channels=1,
    conv_layers=(
        ConvLayerSpec(n_out=20, kernel=5, padding="VALID"),
        ConvLayerSpec(n_out=50, kernel=5, padding="VALID"),
    ),
    fc_dims=(500,),
    n_classes=10,
)

CIFAR10 = CNNTopology(
    name="cifar10",
    input_hw=32,
    input_channels=3,
    conv_layers=(
        ConvLayerSpec(n_out=32, kernel=5, padding="SAME"),
        ConvLayerSpec(n_out=32, kernel=5, padding="SAME"),
        ConvLayerSpec(n_out=64, kernel=5, padding="SAME"),
    ),
    fc_dims=(64,),
    n_classes=10,
)

SVHN = dataclasses.replace(CIFAR10, name="svhn")

PAPER_TOPOLOGIES = {"lenet5": LENET5, "cifar10": CIFAR10, "svhn": SVHN}

# Caffe's cifar10_full: 5x5 SAME convs with OVERLAPPING 3x3/stride-2
# max-pool (32 -> 15 -> 7 -> 3) — the pool-window != pool-stride case the
# paper topologies never exercise.
CIFAR10_FULL = CNNTopology(
    name="cifar10_full",
    input_hw=32,
    input_channels=3,
    conv_layers=(
        ConvLayerSpec(n_out=32, kernel=5, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
        ConvLayerSpec(n_out=32, kernel=5, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
        ConvLayerSpec(n_out=64, kernel=5, padding="SAME", pool=3,
                      pool_stride=2, act="relu"),
    ),
    fc_dims=(64,),
    n_classes=10,
)

# Stride-2 downsampling variant: the first two layers downsample with conv
# stride instead of pooling (32 -> 16 -> 8), the last keeps a 2x2/2 pool.
CIFAR10_STRIDED = CNNTopology(
    name="cifar10_strided",
    input_hw=32,
    input_channels=3,
    conv_layers=(
        ConvLayerSpec(n_out=32, kernel=5, padding="SAME", stride=2, pool=0,
                      act="relu"),
        ConvLayerSpec(n_out=64, kernel=3, padding="SAME", stride=2, pool=0,
                      act="relu"),
        ConvLayerSpec(n_out=64, kernel=3, padding="SAME", pool=2,
                      act="relu"),
    ),
    fc_dims=(64,),
    n_classes=10,
)



def resnet_layers(widths: Sequence[int], blocks_per_stage: int = 2) -> tuple:
    """The stem and basic blocks of a ResNet (He et al., Table 1): a 7x7/2
    conv of ``widths[0]`` with batch norm, ReLU and a 3x3/2 max-pool
    padded by 1, then per width a stage of basic blocks (3x3 conv, BN,
    ReLU, 3x3 conv, BN, join, ReLU). Every stage after the first opens
    with a stride-2 conv and a 1x1/2 projection shortcut (option B)."""

    def conv(n, k, stride=1, join="none", pool=0, pool_stride=None,
             pool_pad=0):
        return ConvLayerSpec(
            n_out=n, kernel=k, padding="SAME", stride=stride, pool=pool,
            pool_stride=pool_stride, pool_pad=pool_pad, act="relu", bn=True,
            join=join,
        )

    layers = [conv(widths[0], 7, stride=2, pool=3, pool_stride=2, pool_pad=1)]
    c = widths[0]
    for si, n in enumerate(widths):
        for bi in range(blocks_per_stage):
            s = 2 if si and not bi else 1
            join = "project" if (s != 1 or c != n) else "identity"
            layers += [conv(n, 3, stride=s), conv(n, 3, join=join)]
            c = n
    return tuple(layers)


# ResNet-18 (arXiv:1512.03385, Table 1, "18-layer"; option B shortcuts):
# 224 -> 112 (stem conv) -> 56 (pool) -> 56, 28, 14, 7 -> global average
# pool -> FC-1000. 1,813,561,344 conv multiply-adds a frame.
RESNET18 = CNNTopology(
    name="resnet18",
    input_hw=224,
    input_channels=3,
    conv_layers=resnet_layers((64, 128, 256, 512)),
    fc_dims=(),
    n_classes=1000,
    global_pool=True,
)

EXTRA_TOPOLOGIES = {
    "cifar10_full": CIFAR10_FULL,
    "cifar10_strided": CIFAR10_STRIDED,
}
CHAIN_TOPOLOGIES = {**PAPER_TOPOLOGIES, **EXTRA_TOPOLOGIES}
ALL_TOPOLOGIES = {**CHAIN_TOPOLOGIES, "resnet18": RESNET18}


def _act(name: str) -> Callable:
    return {"tanh": jnp.tanh, "relu": jax.nn.relu, "none": lambda x: x}[name]


def _init_conv(key, k: int, c: int, n: int, bn: bool, dtype) -> dict:
    """He-normal HWIO kernel, zero bias and, with ``bn``, the identity
    batch norm (gamma 1, beta 0, running mean 0, running variance 1)."""
    w = jax.random.normal(key, (k, k, c, n), dtype) * jnp.sqrt(2.0 / (k * k * c))
    p = {"w": w, "b": jnp.zeros((n,), dtype)}
    if bn:
        p.update(
            gamma=jnp.ones((n,), dtype), beta=jnp.zeros((n,), dtype),
            mean=jnp.zeros((n,), dtype), var=jnp.ones((n,), dtype),
        )
    return p


def init_cnn(key: jax.Array, topo: CNNTopology, dtype=jnp.float32) -> dict:
    """He-init parameters for a topology. Layout:
    conv kernels HWIO (K, K, C, N); FC weights (in, out); a ``project``
    join's 1x1 conv under the layer's ``proj``."""
    params: dict = {"conv": [], "fc": []}
    c = topo.input_channels
    proj = topo.projection_shapes()
    for li, spec in enumerate(topo.conv_layers):
        key, wk, pk = jax.random.split(key, 3)
        p = _init_conv(wk, spec.kernel, c, spec.n_out, spec.bn, dtype)
        if li in proj:
            p["proj"] = _init_conv(pk, 1, proj[li][0], spec.n_out, spec.bn, dtype)
        params["conv"].append(p)
        c = spec.n_out
    dims = (topo.head_in_dim(),) + tuple(topo.fc_dims) + (topo.n_classes,)
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, wk = jax.random.split(key)
        w = jax.random.normal(wk, (d_in, d_out), dtype) * jnp.sqrt(2.0 / d_in)
        params["fc"].append({"w": w, "b": jnp.zeros((d_out,), dtype)})
    return params


def _maxpool(
    x: jax.Array, window: int, stride: int | None = None, pad: int = 0
) -> jax.Array:
    stride = stride or window
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding=((0, 0), (pad, pad), (pad, pad), (0, 0)),
    )


def _batch_norm(y: jax.Array, p: dict) -> jax.Array:
    """Eval-mode batch norm with the layer's running statistics."""
    return (y - p["mean"]) / jnp.sqrt(p["var"] + BN_EPS) * p["gamma"] + p["beta"]


def fold_bn(p: dict) -> dict:
    """A conv's parameters with its eval-mode batch norm folded into the
    kernel and bias (per output channel ``g = gamma / sqrt(var + eps)``:
    ``w * g`` and ``(b - mean) * g + beta``), the projection's too. A
    dict without batch norm comes back with just its ``w``, ``b`` (and
    folded ``proj``). Deployments quantize the folded kernel.

    The fold runs on the host in float64 and is rounded once to float32,
    as offline folding does: in float32 its last bit depends on how a
    compiler fuses the arithmetic (jitted whole and op by op, the same
    float32 fold differs in the last bit of about 40 % of ResNet-18's
    folded weights), and that bit moves a few of the 8-bit codes. It
    therefore takes concrete arrays, not tracers."""
    out = {"w": p["w"], "b": p["b"]}
    if "gamma" in p:
        f = {k: np.asarray(p[k], np.float64)
             for k in ("w", "b", "gamma", "beta", "mean", "var")}
        g = f["gamma"] / np.sqrt(f["var"] + BN_EPS)
        out = {
            "w": jnp.asarray((f["w"] * g).astype(np.float32)),
            "b": jnp.asarray(((f["b"] - f["mean"]) * g + f["beta"]).astype(np.float32)),
        }
    if "proj" in p:
        out["proj"] = fold_bn(p["proj"])
    return out


def quantize_cnn_params(params: dict, bits: int) -> dict:
    """Fake-quantize all parameters with per-tensor dynamic power-of-two
    scales (trace-compatible, STE gradients)."""
    return jax.tree_util.tree_map(lambda p: fake_quant_dynamic(p, bits), params)


def export_cnn_specs(params: dict, bits: int) -> dict:
    """Static per-tensor FixedPointSpec tree for a *trained* model (the
    offline Q-format the paper's synthesis flow consumes)."""
    return jax.tree_util.tree_map(
        lambda p: FixedPointSpec.for_tensor(p, bits),
        params,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )


def cnn_apply(
    params: dict,
    topo: CNNTopology,
    x: jax.Array,
    *,
    weight_bits: int | None = None,
    act_bits: int | None = None,
    pow2_weights: bool = False,
    conv_backend: str | None = None,
    vmem_budget: int | None = None,
) -> jax.Array:
    """Forward pass. x: (B, H, W, C) NHWC. Returns logits (B, n_classes).

    Lowers through the DHM compiler: topology + params + quantization spec
    become a single-device :class:`~repro.core.dhm.compiler.CompiledDHM`
    plan of fused actor-chain stages, which is then run on ``x``.

    ``weight_bits`` enables fixed-point fake-quant of all parameters (QAT
    via STE); ``act_bits`` additionally quantizes the inter-layer feature
    streams — inside the fused kernel epilogue, the paper's quantized pixel
    flow. ``pow2_weights`` projects every weight onto the {0, ±2^k}
    codebook with STE and lowers the FC head through the packed
    ``pow2_matmul`` kernel (beyond-paper: 100%-multiplierless QAT).
    ``conv_backend`` (a ``repro.kernels.backends`` name) selects the kernel
    backend for every conv stage; None means the ``ref`` composition
    (lax.conv — the fast path for training, with well-tuned gradients).
    ``vmem_budget`` is the compiler's cross-layer fusion budget in bytes
    (None = the default, which fuses every paper topology's feature
    extractor into one kernel group; 0 = per-layer stages).
    """
    from repro.core.dhm.compiler import QuantSpec, compile_dhm
    from repro.core.dhm.engine import forward as engine_forward

    plan = compile_dhm(
        topo,
        params,
        quant=QuantSpec(
            weight_bits=weight_bits,
            act_bits=act_bits,
            pow2_weights=pow2_weights,
        ),
        backend=conv_backend if conv_backend is not None else "ref",
        vmem_budget=vmem_budget,
    )
    # Run through the engine's EAGER path rather than plan.__call__:
    # eager model-level calls build a fresh plan per invocation, so the
    # plan-level cached jit would retrace every call — the stage bodies
    # are module-level jitted kernels with process-wide caches instead.
    return engine_forward(plan, x)


def cnn_apply_reference(
    params: dict,
    topo: CNNTopology,
    x: jax.Array,
    *,
    weight_bits: int | None = None,
    act_bits: int | None = None,
    pow2_weights: bool = False,
) -> jax.Array:
    """The hand-composed forward pass (separate conv / bias / batch norm /
    join / pool / act / fake-quant XLA ops) — the oracle every compiled
    plan is tested against. Kept free of the compiler and the fused
    kernels on purpose.

    In float, batch norm runs as its own op after the conv, unfolded. A
    quantized reference quantizes what deployments quantize, the kernel
    with its batch norm folded in (:func:`fold_bn`). A join adds the
    block input (or its projection) to the conv output before pooling
    and the activation; the stream is quantized after the activation,
    as everywhere else."""
    if weight_bits is not None or pow2_weights:
        params = {**params, "conv": [fold_bn(p) for p in params["conv"]]}
    if pow2_weights:
        from repro.core.quant.pow2 import project_pow2_ste

        params = jax.tree_util.tree_map(
            lambda p: project_pow2_ste(p) if p.ndim > 1 else p, params
        )
    if weight_bits is not None:
        params = quantize_cnn_params(params, weight_bits)

    def maybe_qact(h):
        if act_bits is None:
            return h
        spec = FixedPointSpec(bits=act_bits, frac_bits=act_bits - 2)
        return fake_quant_ste(h, spec)

    def conv(h, p, stride, padding):
        h = jax.lax.conv_general_dilated(
            h,
            p["w"],
            window_strides=(stride, stride),
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        h = h + p["b"]
        return _batch_norm(h, p) if "gamma" in p else h

    h = x
    ins = []  # each layer's input: the block inputs the joins add
    proj = topo.projection_shapes()
    for li, (spec, p) in enumerate(zip(topo.conv_layers, params["conv"])):
        ins.append(h)
        h = conv(h, p, spec.stride, spec.padding)
        if spec.join != "none":
            src = ins[li - BLOCK_SPAN + 1]
            if spec.join == "project":
                src = conv(src, p["proj"], proj[li][5], "VALID")
            h = h + src
        pw, ps = spec.pool_cfg
        if pw:
            h = _maxpool(h, pw, ps, spec.pool_pad)
        h = _act(spec.act)(h)
        h = maybe_qact(h)
    if topo.global_pool:
        h = jnp.mean(h, axis=(1, 2))
    h = h.reshape(h.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        h = h @ p["w"] + p["b"]
        if i < len(params["fc"]) - 1:
            h = jnp.tanh(h)
            h = maybe_qact(h)
    return h
