"""Ahead-of-time v5e compiles of the main path at real widths.

Each test compiles a compiled plan's jitted forward for one described (not
attached) v5e chip, with compiled Pallas forced on, and checks that Mosaic
produced the kernels (``tpu_custom_call`` in the compiled HLO). This is
what Mosaic would refuse on the chip — block shapes off the (8, 128)
tiling, strided value slices, packed-int8 reshapes — caught without a
chip. Nothing runs, so the tests say nothing about values.

The topology is described inside a module fixture (never at import), so
only the pytest worker that runs this file loads the TPU compiler; where
it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dhm.compiler import QuantSpec, compile_dhm
from repro.models.cnn import ALL_TOPOLOGIES, init_cnn

INT8 = QuantSpec(weight_bits=6, act_bits=6, int8_compute=True)
BATCH = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Route the ``pallas`` backend through Mosaic as on a TPU host, with
    the persistent compile cache off (entries compiled for a described
    chip cannot be read back) and JAX's trace caches cleared on both
    sides, so no CPU-path trace leaks in and no Mosaic trace leaks out."""
    from jax.experimental.compilation_cache import compilation_cache

    import repro.kernels.pow2_matmul.ops as pow2_ops
    import repro.kernels.stream_conv.ops as conv_ops

    for mod in (conv_ops, pow2_ops):
        monkeypatch.setattr(mod, "compiled_pallas_available", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_kernels(plan, sharding) -> int:
    """Compile the plan's serving forward for the described chip; return
    the number of Mosaic kernels in the compiled HLO."""
    topo = plan.topo
    h, w = topo.input_shape
    x = jax.ShapeDtypeStruct(
        (BATCH, h, w, topo.input_channels), jnp.float32, sharding=sharding
    )
    text = plan.jitted_forward(donate=True).lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    return text.count('custom_call_target="tpu_custom_call"')


def _plan(name, **kw):
    topo = ALL_TOPOLOGIES[name]
    return compile_dhm(topo, init_cnn(jax.random.PRNGKey(0), topo), **kw)


@pytest.mark.parametrize("quant", ["fp32", "int8"])
@pytest.mark.parametrize(
    "name", ["lenet5", "cifar10", "cifar10_full", "cifar10_strided"]
)
def test_fused_plan_compiles(name, quant, one_chip, mosaic):
    """The default plan: the whole feature extractor as one fused
    pyramid kernel. cifar10_full (overlapping 3x3/2 pool, odd widths) and
    cifar10_strided (stride-2 taps) reach the strided views and the int8
    row merges that the paper nets do not."""
    plan = _plan(name, quant=INT8 if quant == "int8" else QuantSpec())
    assert len(plan.fusion_groups) == 1
    assert _compile_kernels(plan, one_chip) == 1


@pytest.mark.parametrize("quant", ["fp32", "int8"])
@pytest.mark.parametrize("name", ["lenet5", "cifar10"])
def test_per_layer_plan_compiles(name, quant, one_chip, mosaic):
    """``vmem_budget=0``: one ``stream_conv_block`` kernel per conv layer
    (the Engine's per-layer rung)."""
    plan = _plan(
        name, quant=INT8 if quant == "int8" else QuantSpec(), vmem_budget=0
    )
    n_conv = len(plan.topo.conv_layers)
    assert _compile_kernels(plan, one_chip) == n_conv


@pytest.mark.parametrize("name", ["lenet5", "cifar10"])
def test_packed_pow2_head_compiles(name, one_chip, mosaic):
    """A pow2 plan lowers each FC layer of the head through the packed
    ``pow2_matmul`` kernel, after the fused conv group."""
    plan = _plan(name, quant=QuantSpec(pow2_weights=True, act_bits=6))
    assert plan.quant.packed_fc_head
    n_fc = len(plan.topo.fc_dims) + 1
    assert _compile_kernels(plan, one_chip) == 1 + n_fc


def test_resnet18_plan_compiles(one_chip, mosaic):
    """ResNet-18 at 224 px in int8: the stem's row-blocked kernel (its
    7x7/2 conv folded space-to-depth, its padded pool) and one residual
    kernel per group of whole basic blocks, each named after its layers,
    fit Mosaic's VMEM and tiling rules."""
    import re

    plan = _plan("resnet18", quant=QuantSpec(weight_bits=8, act_bits=8,
                                             int8_compute=True))
    groups = plan.fusion_groups
    x = jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32, sharding=one_chip)
    text = plan.jitted_forward(donate=True).lower(x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(groups)
    names = set(re.findall(r"%(stream_conv_residual_pallas_l\d+_\d+)\.\d+ = ", text))
    assert names == {
        f"stream_conv_residual_pallas_l{g.layers[0]}_{g.layers[-1]}"
        for g in groups[1:]
    }
