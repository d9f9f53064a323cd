"""Batched serving example: continuous batcher over a reduced model.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-2.7b
"""
import argparse
import time

import numpy as np

import jax

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import Batcher, GenerationConfig, Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS, default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.reduced_config(args.arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    print(f"=== single-stream generation ({cfg.name}) ===")
    eng = ServeEngine(cfg, params, GenerationConfig(
        max_new_tokens=args.new_tokens, cache_len=128, temperature=0.8, top_k=50))
    prompt = rng.integers(0, cfg.vocab_size, (2, 10)).astype(np.int32)
    t0 = time.perf_counter()
    out = eng.generate(prompt, seed=1)
    print(f"  sampled continuations {out.shape} in {time.perf_counter()-t0:.2f}s")
    print(f"  tokens[0]: {out[0].tolist()}")

    print(f"\n=== continuous batching ({args.requests} requests, 3 slots) ===")
    batcher = Batcher(cfg, params, n_slots=3, gcfg=GenerationConfig(cache_len=128))
    prompt1 = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    for rid in range(args.requests):
        batcher.submit(Request(rid=rid, prompt=prompt1,
                               max_new_tokens=args.new_tokens))
    t0 = time.perf_counter()
    done = batcher.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in done)
    print(f"  completed {len(done)} requests / {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
