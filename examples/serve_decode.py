"""Serve a (reduced) assigned LM with batched decode requests.

Demonstrates prefill -> token-by-token decode through the KV-cache /
recurrent-state path for any --arch, including the attention-free rwkv6
whose state stays O(1) with context length.

    PYTHONPATH=src python examples/serve_decode.py --arch rwkv6-7b --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_config, list_configs
from repro.models import encdec, lm, steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=list_configs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch).reduced()
    key = jax.random.key(0)
    init = encdec.init_params if cfg.enc_dec else lm.init_params
    params = init(key, cfg)
    B, P = args.batch, args.prompt_len
    total = P + args.tokens

    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab)
    decode = jax.jit(steps.make_decode_step(cfg))

    if cfg.enc_dec:
        frames = jax.random.normal(jax.random.key(2), (B, total, cfg.d_model),
                                   jnp.float32).astype(cfg.dtype)
        enc_out = encdec.encode(params, cfg, frames)
        ck, cv = encdec.build_cross_cache(params, cfg, enc_out)
        cache = encdec.init_cache(cfg, B, total, total)
        cache["cross_k"], cache["cross_v"] = ck, cv
        start = 0
    else:
        x = lm.embed_tokens(params, cfg, prompt)
        _, cache = lm.prefill(params, cfg, x, extra_len=args.tokens, q_chunk=16)
        if cfg.block == "rwkv" or cfg.pattern:
            pass                         # recurrent state carries the prompt
        start = P

    tok = prompt[:, -1:]
    out_tokens = []
    t0 = time.perf_counter()
    for t in range(args.tokens):
        logits, cache = decode(params, cache, tok, jnp.int32(start + t))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.perf_counter() - t0
    out = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={args.arch} family={cfg.family}")
    print(f"decoded {args.tokens} tokens x batch {B} in {dt:.2f}s "
          f"({B * args.tokens / dt:.0f} tok/s on CPU, reduced config)")
    cache_mb = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache)) / 1e6
    print(f"serving state size: {cache_mb:.2f} MB "
          f"({'O(1) in context' if cfg.subquadratic else 'KV grows with context'})")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
