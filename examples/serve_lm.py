"""Batched serving with continuous batching (the cloud-oracle path).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-12b --requests 8

Eight prompts share 4 decode slots; finished sequences free their slot
immediately for waiting requests (vLLM-style, shape-static so the
decode step compiles once). Greedy decode is bit-exact with a full
re-forward (tests/test_serving.py)."""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.launch import compile_cache
from repro.models import layers, transformer
from repro.serving.engine import ServeEngine


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch).scaled(remat=False)
    print(f"arch={args.arch} (reduced config: {cfg.num_layers}L "
          f"d={cfg.d_model} vocab={cfg.vocab_size})")
    params = layers.split_annotated(
        transformer.init_model(cfg, jax.random.PRNGKey(0)))[0]

    eng = ServeEngine(cfg, params, slots=args.slots, cache_len=256,
                      temperature=args.temperature)
    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(4, 24))
        rids.append(eng.submit(prompt, max_new=args.max_new))
    results = eng.run()
    dt = time.time() - t0
    total_new = sum(len(v) for v in results.values())
    for rid in rids:
        out = results[rid]
        print(f"  req {rid}: {len(out)} tokens -> {out[:8]}{'...' if len(out) > 8 else ''}")
    print(f"{args.requests} requests, {total_new} tokens in {dt:.1f}s "
          f"({total_new / dt:.1f} tok/s on CPU with {args.slots} slots)")


if __name__ == "__main__":
    main()
