"""Batched serving demo: prefill a batch of prompts, decode with greedy or
temperature sampling, optionally with pow2-packed ("constant-specialized")
weights for every linear layer — the paper's tactic as an LM serving
feature (4 bits/weight).

    PYTHONPATH=src python examples/serve.py --batch 4 --new-tokens 16
    PYTHONPATH=src python examples/serve.py --pow2
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.models import transformer as T
from repro.models.layers import pack_params_pow2

# Pack every linear in the stack to pow2 codes (serving format). Stacked
# scan-layer weights are handled inside pack_linear_pow2 (per-layer
# scales via vmap, odd widths zero-padded) — the packing logic lives in
# repro.models.layers, shared with the single-linear path.
quantize_stack_pow2 = pack_params_pow2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pow2", action="store_true",
                    help="serve with pow2-packed weights (paper tactic)")
    args = ap.parse_args()

    cfg = get_arch(args.arch).scaled_down(n_layers=4, d_model=128,
                                          vocab_size=1024)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.pow2:
        n_before = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(params["stack"])
        )
        params = dict(params, stack=quantize_stack_pow2(params["stack"]))
        n_after = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(params["stack"])
        )
        print(f"pow2-packed stack: {n_before/1e6:.1f} MB -> "
              f"{n_after/1e6:.1f} MB ({n_before/n_after:.2f}x)")

    b, p_len = args.batch, args.prompt_len
    prompts = jax.random.randint(jax.random.PRNGKey(1), (b, p_len), 0,
                                 cfg.vocab_size)
    max_len = p_len + args.new_tokens + 1

    t0 = time.time()
    logits, cache = T.prefill(params, cfg, prompts, max_len=max_len)
    prefill_s = time.time() - t0
    print(f"prefill: batch={b} len={p_len} in {prefill_s*1e3:.0f} ms "
          f"({b*p_len/prefill_s:.0f} tok/s)")

    decode = jax.jit(
        lambda pr, tok, cache, idx: T.decode_step(pr, cfg, tok, cache, idx)
    )
    key = jax.random.PRNGKey(2)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for t in range(args.new_tokens):
        logits, cache = decode(params, tok, cache, jnp.asarray(p_len + t))
        if args.temperature > 0:
            key, sk = jax.random.split(key)
            tok = jax.random.categorical(
                sk, logits / args.temperature, axis=-1
            )[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    decode_s = time.time() - t0
    seqs = jnp.concatenate(out_tokens, axis=1)
    print(f"decode: {args.new_tokens} tokens x {b} seqs in "
          f"{decode_s*1e3:.0f} ms ({b*args.new_tokens/decode_s:.1f} tok/s)")
    print("sample continuation:", [int(t) for t in seqs[0][:12]])
    print("OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
