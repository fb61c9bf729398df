"""End-to-end training driver: data pipeline -> sharded step -> AdamW ->
checkpoint/restart, with fault tolerance and straggler monitoring.

    PYTHONPATH=src python examples/train_lm.py --preset smoke
    PYTHONPATH=src python examples/train_lm.py --preset 20m --steps 200
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is the deliverable configuration (~100M params); on this
1-core CPU container it runs at minutes/step, so CI uses `smoke` and the
recorded convergence run uses `20m` (see EXPERIMENTS.md §Training).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs.base import ArchConfig
from repro.data import ShardedLoader, TokenStreamConfig, synthetic_token_batches
from repro.models import transformer as T
from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    linear_warmup_cosine,
)
from repro.runtime import FaultInjector, ResilientTrainer, StragglerMonitor

PRESETS = {
    # name: (layers, d_model, heads, kv, d_ff, vocab, seq, batch)
    # The synthetic affine-recurrence task is a vocab-sized lookup, so the
    # vocab is kept small enough that each embedding row gets O(100s) of
    # gradient updates within a few-hundred-step run.
    "smoke": (2, 128, 4, 2, 256, 512, 64, 8),
    "20m": (8, 384, 8, 4, 1024, 2048, 256, 8),
    "100m": (12, 768, 12, 4, 2048, 32768, 512, 8),
}


def make_cfg(preset: str) -> ArchConfig:
    l, d, h, kv, ff, v, _, _ = PRESETS[preset]
    return ArchConfig(
        name=f"lm-{preset}",
        family="dense",
        n_layers=l,
        d_model=d,
        n_heads=h,
        n_kv_heads=kv,
        d_ff=ff,
        vocab_size=v,
        dtype="float32",
        remat="none",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="results/train_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a node failure at this step")
    args = ap.parse_args()

    cfg = make_cfg(args.preset)
    _, _, _, _, _, _, seq, batch = PRESETS[args.preset]
    from repro.models.accounting import param_count

    n_params = param_count(cfg)
    print(f"model: {cfg.n_layers}L d={cfg.d_model} -> {n_params/1e6:.1f}M params")

    stream_cfg = TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, batch_size=batch
    )
    print(f"stream loss floor: {stream_cfg.loss_floor:.3f} nats")

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(weight_decay=0.01)
    opt = adamw_init(params, opt_cfg)
    sched = linear_warmup_cosine(1e-3, warmup_steps=20, total_steps=args.steps)

    @jax.jit
    def jit_step(params, opt, tokens, step_idx):
        def loss_fn(p):
            loss, m = T.train_loss(
                p, cfg, {"tokens": tokens},
                vocab_chunk=min(8192, cfg.vocab_size),
            )
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(grads, opt, params, opt_cfg,
                                   sched(step_idx))
        return params, opt, loss, gnorm

    def batch_fn(step):
        # Deterministic per-step stream => bitwise replay after restart.
        it = synthetic_token_batches(stream_cfg, seed=1000 + step)
        return jnp.asarray(next(it)["tokens"])

    tokens_per_step = batch * seq
    losses = []

    def step_fn(state, tokens, step):
        params, opt = state
        t0 = time.time()
        params, opt, loss, gnorm = jit_step(params, opt, tokens,
                                            jnp.asarray(step))
        loss = float(loss)
        losses.append(loss)
        dt = time.time() - t0
        if step % 10 == 0 or step == args.steps - 1:
            print(
                f"step {step:4d} loss {loss:.4f} gnorm {float(gnorm):.2f} "
                f"{tokens_per_step / dt:.0f} tok/s",
                flush=True,
            )
        return (params, opt), {"loss": loss}

    injector = (
        FaultInjector(fail_at_steps=(args.inject_failure_at,))
        if args.inject_failure_at >= 0
        else None
    )
    trainer = ResilientTrainer(
        step_fn,
        batch_fn,
        CheckpointManager(args.ckpt_dir, keep=2),
        ckpt_every=args.ckpt_every,
        straggler=StragglerMonitor(),
        fault_injector=injector,
    )
    t0 = time.time()
    (params, opt), last = trainer.run((params, opt), num_steps=args.steps)
    wall = time.time() - t0
    print(
        f"done: {args.steps} steps in {wall:.1f}s "
        f"({args.steps * tokens_per_step / wall:.0f} tok/s), "
        f"final loss {losses[-1]:.4f} (floor {stream_cfg.loss_floor:.3f}), "
        f"restarts={trainer.restarts}, stragglers={len(trainer.straggler.flagged)}"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
