"""Helios applied to LM training: out-of-core token pipeline + expert-hotness
tiering + fault-tolerant training loop (checkpoint / straggler / restart).

    PYTHONPATH=src python examples/train_llm_tiered.py --steps 60
"""
import argparse
import tempfile
import time

import jax
import numpy as np

from repro import compile_cache
from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.hotness import token_hotness
from repro.data.tokens import OutOfCoreTokenIterator, TokenStore
from repro.ft.failures import Coordinator
from repro.models import lm, steps
from repro.train.optim import adamw, warmup_cosine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    args = ap.parse_args()
    compile_cache.enable()

    root = tempfile.mkdtemp(prefix="helios_llm_")
    cfg = get_config(args.arch).reduced()
    store = TokenStore(f"{root}/tokens", n_sequences=256, seq_len=32,
                       vocab=cfg.vocab, n_shards=4, create=True)
    it = OutOfCoreTokenIterator(store, batch_size=16, n_microbatches=2)

    # token-frequency hotness drives the embedding-row tier placement
    sample = store.read_rows(np.arange(64))
    hot = token_hotness(sample.astype(np.int64), cfg.vocab)
    print(f"token hotness: top-1% of vocab covers "
          f"{hot[np.argsort(-hot)[:cfg.vocab // 100]].sum() / hot.sum():.0%}"
          " of accesses")

    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw(warmup_cosine(1e-3, 10, args.steps))
    state = {"params": params, "opt": opt.init(params)}
    train = jax.jit(steps.make_train_step(cfg, opt, q_chunk=16))

    mgr = CheckpointManager(f"{root}/ckpt", keep=2)
    coord = Coordinator(n_workers=1)
    losses = []
    for step in range(args.steps):
        t0 = time.perf_counter()
        coord.heartbeat(0)
        state, m = train(state, next(it))
        losses.append(float(m["loss"]))
        plan = coord.observe_stage(step, "train", time.perf_counter() - t0)
        if plan["action"] != "ok":
            print(f"  step {step}: straggler detected -> {plan}")
        if step % 20 == 19:
            mgr.save(step, state, extra={"data_iter": it.checkpoint_state()})
            print(f"step {step:3d} loss {losses[-1]:.3f} (async checkpoint)")
    mgr.wait()
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps; "
          f"checkpoints at steps {mgr.all_steps()}")
    restored, extra = mgr.restore()
    print(f"restore ok: step {extra['step']}, data cursor "
          f"{extra['data_iter']['cursor']}")


if __name__ == "__main__":
    main()
