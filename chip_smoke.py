#!/usr/bin/env python3
"""Chip smoke test: the DHM serving path, end to end, on a TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --four-chips      # the 2-stage x 2-data mesh only

One process, the normal entry points, no device fallback. With no option
it compiles the paper's CIFAR-10 net (``cifar10``: 32x32x3 frames, conv
32-32-64 with 5x5 kernels, FC-64, 10 classes) at full width from seeded
random weights, twice — fp32 ``QuantSpec()`` and true int8
``QuantSpec(weight_bits=6, act_bits=6, int8_compute=True)`` — and for each
plan:

- serves 16 requests of 1-8 frames through ``Engine(plan, microbatch=8)``
  and checks that every request answered, on rung ``fused``, with no
  demotion;
- checks that the compiled HLO of the plan's jitted forward holds one
  Mosaic kernel (``tpu_custom_call``) per fusion group, so interpret mode
  and the XLA fallback are not in play;
- compares the served logits with the same plan compiled with
  ``backend="ref"`` (plain ``lax.conv``), run on the chip. The int8 logits
  must be bit-equal: the frames are on the input grid, the integer convs
  are exact, and both plans share the XLA head. The fp32 reference runs
  at ``highest`` matmul precision and the fp32 logits must agree within
  ``FP32_TOL`` of the largest reference logit (see there).

``--four-chips`` runs only the spatial pipeline: ``cifar10`` with
``n_stages=2`` behind ``Engine(plan, mesh=<2 stage x 2 data>,
data_axis="data", n_microbatches=4)``, checked for rung ``mesh``, stage
parameters on all 4 devices, and logits equal to the same plan run on one
device.

Each phase prints one line of results; the last line of standard output
is the JSON verdict with the device as JAX reports it. Any failed check
exits non-zero before that line. Without a TPU it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

# A bf16 MXU pass rounds each operand to 8 significant bits (relative
# error 2^-9), so one matmul layer is off by at most ~2 * 2^-9 of its
# output scale; tanh and max-pool do not amplify it. Four matmul layers
# (three convs and the FC head, whose hidden FC-64 feeds the logits
# directly) bound the fp32 logits at 8 * 2^-9 = 2^-6 of the largest
# logit, against a reference computed at full fp32 precision.
FP32_TOL = 2.0**-6
N_REQUESTS = 16
MICROBATCH = 8


def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _tpu_devices():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devs[0].platform} devices")
    return devs


def _requests(rng, frame_shape, grid_scale):
    """N_REQUESTS requests of 1-8 frames each, snapped onto the input
    stream grid (values k * grid_scale in the 6-bit range)."""
    out = []
    for _ in range(N_REQUESTS):
        n = int(rng.integers(1, MICROBATCH + 1))
        codes = np.clip(
            np.round(rng.standard_normal((n,) + frame_shape) / grid_scale),
            -32, 31,
        )
        out.append((codes * grid_scale).astype(np.float32))
    return out


def _compile_forward(plan, frame_shape):
    """Compile the plan's jitted serving forward at the micro-batch shape;
    returns (seconds, Mosaic kernels in the compiled HLO)."""
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((MICROBATCH,) + frame_shape, jnp.float32)
    t0 = time.perf_counter()
    text = plan.jitted_forward(donate=True).lower(x).compile().as_text()
    seconds = time.perf_counter() - t0
    return seconds, text.count('custom_call_target="tpu_custom_call"')


def _ref_logits(ref_plan, frames):
    """The reference plan over the Engine's own packing: all frames in
    order, micro-batches of MICROBATCH with a zero-padded tail."""
    n = frames.shape[0]
    pad = -n % MICROBATCH
    tail = np.zeros((pad,) + frames.shape[1:], frames.dtype)
    x = np.concatenate([frames, tail])
    outs = [
        np.asarray(ref_plan(x[i : i + MICROBATCH]))
        for i in range(0, x.shape[0], MICROBATCH)
    ]
    return np.concatenate(outs)[:n]


def one_chip(seed: int) -> None:
    import jax

    from repro.core.dhm import Engine, QuantSpec, compile_dhm
    from repro.models.cnn import CIFAR10, init_cnn

    topo = CIFAR10
    params = init_cnn(jax.random.PRNGKey(seed), topo)
    frame_shape = topo.input_shape + (topo.input_channels,)
    rng = np.random.default_rng(seed)
    requests = _requests(rng, frame_shape, 2.0**-4)
    frames = np.concatenate(requests)
    for label, quant in (
        ("fp32", QuantSpec()),
        ("int8", QuantSpec(weight_bits=6, act_bits=6, int8_compute=True)),
    ):
        plan = compile_dhm(topo, params, quant=quant)
        n_groups = len(plan.fusion_groups)
        compile_s, n_kernels = _compile_forward(plan, frame_shape)
        if n_kernels != n_groups:
            _fail(
                f"{label}: {n_kernels} tpu_custom_call in the HLO for "
                f"{n_groups} fusion groups"
            )
        t0 = time.perf_counter()
        engine = Engine(plan, microbatch=MICROBATCH)
        start_s = time.perf_counter() - t0
        reqs = [engine.submit(x) for x in requests]
        engine.flush()
        answered = [r for r in reqs if r.ok]
        if len(answered) != len(reqs):
            errs = [str(r.error) for r in reqs if not r.ok]
            _fail(f"{label}: {len(errs)} requests failed: {errs[:3]}")
        if engine.rung != "fused" or engine.demotions:
            _fail(
                f"{label}: serving on rung {engine.rung!r}, demotions "
                f"{engine.demotions}"
            )
        got = np.concatenate([np.asarray(r.result()) for r in reqs])
        ref_plan = compile_dhm(topo, params, quant=quant, backend="ref")
        if label == "int8":
            want = _ref_logits(ref_plan, frames)
            err = float(np.abs(got - want).max())
            ok, tol = np.array_equal(got, want), 0.0
        else:
            with jax.default_matmul_precision("highest"):
                want = _ref_logits(ref_plan, frames)
            err = float(np.abs(got - want).max())
            tol = FP32_TOL * float(np.abs(want).max())
            ok = bool(np.isfinite(got).all()) and err <= tol
        print(
            f"cifar10 {label}: compile_s={compile_s:.3f} "
            f"engine_start_s={start_s:.3f} "
            f"fusion_groups={[g.layers for g in plan.fusion_groups]} "
            f"tpu_custom_call={n_kernels} rung={engine.rung} "
            f"demotions={len(engine.demotions)} "
            f"requests={len(answered)}/{len(reqs)} frames={frames.shape[0]} "
            f"max_abs_err={err!r} tol={tol!r}",
            flush=True,
        )
        if not ok:
            _fail(f"{label}: logits off the ref plan by {err!r} (tol {tol!r})")


def four_chips(seed: int) -> None:
    import jax

    from repro.core.dhm import Engine, compile_dhm
    from repro.core.dhm.pipeline import make_pipeline_mesh
    from repro.models.cnn import CIFAR10, init_cnn

    devs = jax.devices()
    if len(devs) < 4:
        _fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    topo = CIFAR10
    params = init_cnn(jax.random.PRNGKey(seed), topo)
    plan = compile_dhm(topo, params, n_stages=2)
    mesh = make_pipeline_mesh((2, 2), ("stage", "data"), devices=devs[:4])
    n_mb = 4
    t0 = time.perf_counter()
    engine = Engine(
        plan, microbatch=MICROBATCH, mesh=mesh, data_axis="data",
        n_microbatches=n_mb,
    )
    start_s = time.perf_counter() - t0
    if engine.rung != "mesh" or engine.demotions:
        _fail(f"serving on rung {engine.rung!r}, demotions {engine.demotions}")
    # Every stacked parameter leaf spans the 4 devices, and each device
    # holds the slice of its own stage (its row of the mesh).
    stage_of = {d: i for i, row in enumerate(mesh.devices) for d in row}
    leaf_devs = []
    for leaf in engine._runner.stacked_leaves:
        shards = leaf.addressable_shards
        leaf_devs.append({sh.device for sh in shards})
        if leaf_devs[-1] != set(devs[:4]) or any(
            sh.index[0] != slice(stage_of[sh.device], stage_of[sh.device] + 1)
            for sh in shards
        ):
            _fail(
                f"stage parameters {leaf.shape} are not one stage slice per "
                f"device row: {[(sh.device.id, sh.index[0]) for sh in shards]}"
            )
    frame_shape = topo.input_shape + (topo.input_channels,)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((engine.group,) + frame_shape).astype(np.float32)
    got = np.asarray(engine.infer(x))
    if engine.rung != "mesh" or engine.demotions:
        _fail(f"demoted while serving: {engine.demotions}")
    want = np.asarray(plan(x))
    err = float(np.abs(got - want).max())
    print(
        f"cifar10 2-stage x 2-data mesh: engine_start_s={start_s:.3f} "
        f"rung={engine.rung} demotions={len(engine.demotions)} "
        f"n_microbatches={n_mb} frames={x.shape[0]} "
        f"param_devices={sorted(len(d) for d in leaf_devs)} "
        f"max_abs_err_vs_single_device={err!r}",
        flush=True,
    )
    if not np.array_equal(got, want):
        _fail(f"pipelined logits off the single-device plan by {err!r}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the 2-stage x 2-data mesh phase (needs 4 chips)",
    )
    args = ap.parse_args()
    devs = _tpu_devices()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
