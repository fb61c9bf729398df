"""Quickstart: build a small LM, train a few steps, apply the paper's
pow2 (constant-specialized-multiplier) quantization, and serve tokens.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.data import TokenStreamConfig, synthetic_token_batches
from repro.models import transformer as T
from repro.models.layers import pack_linear_pow2
from repro.optim import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm


def main():
    # A reduced qwen2.5 — same family, CPU-sized (the full configs are
    # exercised by the multi-pod dry-run, not on this host).
    cfg = get_arch("qwen2.5-3b").scaled_down(
        n_layers=4, d_model=128, vocab_size=512
    )
    print(f"arch={cfg.name} (reduced): {cfg.n_layers}L d={cfg.d_model}")

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(weight_decay=0.01)
    opt = adamw_init(params, opt_cfg)

    stream_cfg = TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=64, batch_size=16
    )
    batches = synthetic_token_batches(stream_cfg, seed=0)
    print(f"token stream loss floor: {stream_cfg.loss_floor:.3f} nats")

    @jax.jit
    def step(params, opt, tokens):
        def loss_fn(p):
            loss, m = T.train_loss(p, cfg, {"tokens": tokens}, vocab_chunk=256)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(grads, opt, params, opt_cfg,
                                   jnp.asarray(1e-3))
        return params, opt, loss

    t0 = time.time()
    for i in range(30):
        batch = next(batches)
        params, opt, loss = step(params, opt, jnp.asarray(batch["tokens"]))
        if i % 10 == 0 or i == 29:
            print(f"step {i:3d} loss {float(loss):.3f}")
    print(f"trained 30 steps in {time.time() - t0:.1f}s")

    # --- The paper's tactic: constant-specialize the weights (pow2 codes).
    from repro.core.quant.pow2 import pow2_codes
    from repro.core.quant import classify_params
    w = params["stack"]["units"][0]["ffn"]["up"]["w"]
    codes, scale = pow2_codes(w[0], channel_axis=1)
    nz = float(jnp.mean(codes == 0))
    print(f"pow2-quantized ffn/up: {100*nz:.1f}% zero codes, "
          f"4 bits/weight (4x bandwidth saving vs bf16)")

    # --- Serve: prefill + a few greedy decode steps.
    prompt = jnp.asarray(next(batches)["tokens"])[:2, :16]
    logits, cache = T.prefill(params, cfg, prompt, max_len=32)
    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for t in range(8):
        logits, cache = T.decode_step(
            params, cfg, tok, cache, jnp.asarray(16 + t)
        )
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        toks.append(int(tok[0, 0]))
    print("greedy continuation:", toks)
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
