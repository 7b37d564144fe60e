"""End-to-end training driver: train a ~100M-param dense LM (qwen3 family,
reduced) for a few hundred steps on the synthetic Markov corpus, with
checkpointing and CSV metrics.

  PYTHONPATH=src python examples/train_lm.py --steps 300
"""
import argparse
import os
import time

import jax
import numpy as np

from repro.ckpt import load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.data.synthetic import TokenPipelineConfig, token_batch_stream
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--out", default="artifacts/train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("qwen3-1.7b").replace(
        n_layers=args.layers, d_model=args.d_model, n_heads=12, n_kv_heads=4,
        d_head=64, d_ff=4 * args.d_model, vocab_size=args.vocab,
        param_dtype="float32", compute_dtype="float32", remat=False,
        attn_chunk=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"model: {args.layers}L d={args.d_model} -> {n_params/1e6:.1f}M params")

    train_step, opt_init = make_train_step(cfg, base_lr=args.lr, warmup=20,
                                           total=args.steps)
    opt = opt_init(params)
    step_fn = jax.jit(train_step)
    stream = token_batch_stream(TokenPipelineConfig(
        vocab_size=args.vocab, seq_len=args.seq, batch=args.batch))

    os.makedirs(args.out, exist_ok=True)
    csv = open(os.path.join(args.out, "metrics.csv"), "w")
    csv.write("step,loss,ce,grad_norm,lr,ms_per_step\n")
    t_last = time.time()
    for step in range(1, args.steps + 1):
        batch = next(stream)
        params, opt, m = step_fn(params, opt, batch)
        if step % 10 == 0 or step == 1:
            dt = (time.time() - t_last) / (10 if step > 1 else 1) * 1e3
            t_last = time.time()
            print(f"step {step:4d} loss={float(m['loss']):.4f} "
                  f"ce={float(m['ce']):.4f} gnorm={float(m['grad_norm']):.2f} "
                  f"{dt:.0f}ms/step")
            csv.write(f"{step},{float(m['loss']):.5f},{float(m['ce']):.5f},"
                      f"{float(m['grad_norm']):.4f},{float(m['lr']):.2e},"
                      f"{dt:.1f}\n")
            csv.flush()
    save_checkpoint(os.path.join(args.out, "final"), params,
                    step=args.steps, extra={"config": cfg.name})
    print(f"saved checkpoint to {args.out}/final.npz")


if __name__ == "__main__":
    main()
