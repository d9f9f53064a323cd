"""End-to-end LM training example.

Default: a ~10M-param qwen2-family model for 300 steps on CPU (~minutes),
with checkpointing and a mid-run restart to demonstrate exact resume.
``--arch`` picks any of the 10 assigned architectures (reduced config);
``--full`` uses the published config (TPU-scale).

    PYTHONPATH=src python examples/train_lm.py
    PYTHONPATH=src python examples/train_lm.py --arch mixtral-8x7b --steps 100
"""
import argparse
import dataclasses
import tempfile

import jax.numpy as jnp

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.data import DataConfig
from repro.optim import AdamWConfig
from repro.train import TrainConfig, TrainLoopConfig, train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS, default="qwen2-1.5b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--width", type=int, default=256,
                    help="d_model override for the example model (CPU scale)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch) if args.full else configs.reduced_config(args.arch)
    if not args.full and args.width:
        # a slightly larger "example scale" model than the smoke config
        cfg = dataclasses.replace(
            cfg, d_model=args.width, head_dim=max(32, args.width // 8),
            d_ff=2 * args.width if cfg.d_ff else 0, vocab_size=4096,
        )
    print(f"arch={cfg.name} ~{cfg.n_params()/1e6:.1f}M params")

    tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3), remat=None,
                       dtype=jnp.float32)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.batch)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        lcfg = TrainLoopConfig(total_steps=args.steps, ckpt_every=100,
                               ckpt_dir=ckpt_dir, log_every=20)
        state, history = train_loop(cfg, tcfg, dcfg, lcfg)
    first = sum(h["loss"] for h in history[:10]) / max(len(history[:10]), 1)
    last = sum(h["loss"] for h in history[-10:]) / max(len(history[-10:]), 1)
    print(f"\nloss: first10 {first:.4f} -> last10 {last:.4f} "
          f"({'LEARNING' if last < first else 'no improvement'})")


if __name__ == "__main__":
    main()
