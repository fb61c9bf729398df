"""The resnet family: its own plain reference agrees with the program's
oracle, ``cnn_apply_reference``, at a small size; its layers are the
program's ResNet-18; its pricing counts He et al.'s workload; and the
residual kernels' metric readers price calls by the layers named in
their events."""
import json

import numpy as np
import pytest
from jax.profiler import ProfileData

import check
import inputs
import reference
import registry
import tracereduce

resnet = registry.family("resnet")

SMALL = {
    "input_hw": [32, 32],
    "input_channels": 3,
    "stem": {"n_out": 8, "kernel": 7, "stride": 2, "pool": 3,
             "pool_stride": 2, "pool_pad": 1},
    "widths": [8, 16],
    "blocks_per_stage": 1,
    "n_classes": 10,
}


def _config():
    bm = registry.benchmark()
    return registry.config(bm, "resnet18_int8")


def _program_topology(topology):
    from repro.models.cnn import CNNTopology, resnet_layers

    return CNNTopology(
        name="small", input_hw=tuple(topology["input_hw"]),
        input_channels=topology["input_channels"],
        conv_layers=resnet_layers(
            topology["widths"], topology["blocks_per_stage"]
        ),
        fc_dims=(), n_classes=topology["n_classes"], global_pool=True,
    )


def test_resnet18_ops_per_frame():
    """3,628,146,688 ops a frame at 224 px: 1,813,561,344 conv
    multiply-adds with the three projections, and FC-1000 on 512."""
    topo = _config()["topology"]
    assert resnet.frame_ops(topo) == 3_628_146_688
    assert resnet.head_ops(topo) == 1_024_000
    assert resnet.group_ops(topo, 0, 16) == 2 * 1_813_561_344
    assert sum(1 for s in resnet.conv_shapes(topo) if s[5]) == 3


def test_layers_are_the_programs_resnet18():
    """The family's expansion is the program's RESNET18, layer for layer:
    the trace's layer numbers and the pricing refer to the same convs."""
    from repro.models.cnn import RESNET18

    for mine, spec in zip(resnet.layers(_config()["topology"]), RESNET18.conv_layers,
                          strict=True):
        assert (mine["n_out"], mine["kernel"], mine["stride"], mine["pool"],
                mine["pool_pad"], mine["join"]) == (
            spec.n_out, spec.kernel, spec.stride, spec.pool, spec.pool_pad, spec.join
        )
    with pytest.raises(ValueError, match="not implemented"):
        resnet.layers({**SMALL, "groups": 32})


def test_group_bytes_counts_input_output_and_weights():
    topo = _config()["topology"]
    # Layers 1..2 (the first block): 56x56x64 int8 in, 56x56x64 f32 out,
    # two 3x3x64x64 kernels and their biases.
    want = 2 * (56 * 56 * 64 + 56 * 56 * 64 * 4) + 2 * (9 * 64 * 64 + 64 * 4)
    assert resnet.group_bytes(topo, 1, 2, 2, 1) == want
    # Layer 6 closes a block with a 1x1 projection of 64 -> 128 channels.
    one = resnet.group_bytes(topo, 5, 6, 1, 1)
    assert one == (56 * 56 * 64 + 28 * 28 * 128 * 4 + 9 * 64 * 128
                   + 9 * 128 * 128 + 64 * 128 + 4 * 128 * 3)


@pytest.mark.parametrize("bits", [None, 8])
def test_matches_the_program_oracle(bits):
    """At a small size on seeded weights: float with batch norm as its own
    op, and 8-bit with batch norm folded into the quantized kernels."""
    import jax

    from repro.models.cnn import cnn_apply_reference

    rs = inputs.streams(2**33 + 16)
    params = resnet.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(resnet.frame_shape(SMALL), 8, 16, rs["frames"])
    got = reference.logits(params, frames, resnet.forward, SMALL, fake_quant_bits=bits)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(cnn_apply_reference(
            params, _program_topology(SMALL), frames, weight_bits=bits,
            act_bits=bits,
        ))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)


def test_batch_norm_folds_alike_in_the_program_and_the_reference():
    """The folded kernel's 8-bit codes must not hang on how a compiler
    fuses the fold: the reference's fold, jitted whole as the harness
    runs it, and the program's, run at compile time, agree bit for bit
    on a stage-4-sized kernel. A float32 fold fails this: jitted and op
    by op it differs in the last bit of many weights, which moved a few
    codes of ResNet-18 and every logit past 2^-10 of the largest."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import fold_bn

    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    n = 512
    p = {
        "w": 0.03 * jax.random.normal(ks[0], (3, 3, 512, n)),
        "b": jnp.zeros((n,)),
        "gamma": jax.random.uniform(ks[1], (n,), minval=0.2, maxval=0.5),
        "beta": 0.1 * jax.random.normal(ks[2], (n,)),
        "mean": 0.1 * jax.random.normal(ks[3], (n,)),
        "var": jax.random.uniform(ks[4], (n,), minval=0.5, maxval=2.0),
    }
    ref = jax.jit(resnet._fold)(p)
    prog = fold_bn(p)
    for k in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(prog[k]))


def test_program_plan_is_correct_and_a_perturbed_one_is_not():
    """The program's int8 plan, compiled by the family's ``system``,
    reads correct against the family's reference under the
    configuration's limits; the 7-bit control and a perturbed weight
    read false."""
    import jax
    import jax.numpy as jnp

    cfg = {**_config(), "topology": SMALL}
    rs = inputs.streams(2**40 + 3)
    params = resnet.make_params(SMALL, rs["weights"])
    frames = inputs.frame_pool(resnet.frame_shape(SMALL), 8, 16, rs["frames"])
    ref = reference.logits(params, frames, resnet.forward, SMALL, **cfg["reference"])
    limits = {k: v for k, v in cfg["limits"].items() if k != "unanswered"}

    def verdict(logits):
        return check.verdict(check.numbers(np.asarray(logits), ref), limits)

    plan = resnet.system(cfg, params)
    ok, checks = verdict(plan(jnp.asarray(frames)))
    assert ok, checks
    assert resnet.kernel_calls(plan) == len(plan.fusion_groups)
    ctl = reference.logits(params, frames, resnet.forward, SMALL, **cfg["control"])
    assert not verdict(ctl)[0]
    bad = jax.tree_util.tree_map(lambda a: a, params)
    bad["conv"][3]["w"] = bad["conv"][3]["w"] * 1.25
    assert not verdict(resnet.system(cfg, bad)(jnp.asarray(frames)))[0]


US = 1_000_000  # ps per microsecond


def _ev(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _meta(i, name):
    return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'


BLOCK = "%stream_conv_residual_pallas_l1_2.1 = f32[64,56,56,64] custom-call(s8[64,58,58,64] %pad.3)"
STAGE4 = "%stream_conv_residual_pallas_l13_16.4 = f32[64,7,7,512] custom-call(s8[64,16,9,256] %pad.9)"
TRACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_ev(1, 10, 20)} {_ev(2, 40, 10)} {_ev(3, 60, 30)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_ev(4, 0, 2)} {_ev(5, 10, 80)} {_ev(4, 100, 1)}
  }}
  {_meta(1, BLOCK)}
  {_meta(2, STAGE4)}
  {_meta(3, "%stream_conv_fused_pallas.1 = f32[64,56,56,64] custom-call(s8[64,115,115,12] %copy.2)")}
  {_meta(4, "jit_" + tracereduce.MARK + "(77)")}
  {_meta(5, "jit__lambda(824830274744188033)")}
}}
"""


def test_residual_metric_readers_price_each_call_by_its_layers():
    reduced = tracereduce.from_profile(ProfileData.from_text_proto(TRACE))

    class Ctx:
        trace = reduced
        device_kind = "TPU v5 lite"
        cfg = _config()
        family = resnet

    topo = Ctx.cfg["topology"]
    busy = registry.metric_reader("residual_busy_pct").read(Ctx)
    assert busy == pytest.approx(100.0 * 30 / 60)
    ops = 64 * (resnet.group_ops(topo, 1, 2) + resnet.group_ops(topo, 13, 16))
    n_bytes = (resnet.group_bytes(topo, 1, 2, 64, 1)
               + resnet.group_bytes(topo, 13, 16, 64, 1))
    want = 100.0 * max(ops / 393e12, n_bytes / 819e9) / 30e-6
    roof = registry.metric_reader("residual_roofline_pct").read(Ctx)
    assert roof == pytest.approx(want)
    assert json.dumps(roof)
