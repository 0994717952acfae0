"""Serving driver: continuous batching engine (+ optional MCTS decoding).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --requests 8 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke --mcts
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.base import count_params, get_family
from repro.serving.engine import EngineConfig, Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--mcts", action="store_true")
    ap.add_argument("--mcts-budget", type=int, default=16)
    ap.add_argument("--mcts-lanes", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("whisper",):
        raise SystemExit("serve driver targets decoder-only archs; "
                         "whisper decoding runs via examples/")
    fam = get_family(cfg)
    params = fam.init(cfg, jax.random.key(0))
    print(f"arch={cfg.name} params={count_params(params):,}")
    rng = np.random.default_rng(0)

    if args.mcts:
        from repro.serving.mcts_decode import MCTSDecodeConfig, mcts_decode
        prompt = rng.integers(1, cfg.vocab_size, size=args.prompt_len)
        dcfg = MCTSDecodeConfig(budget=args.mcts_budget, lanes=args.mcts_lanes)
        t0 = time.time()
        toks = mcts_decode(cfg, params, prompt, args.max_new, dcfg)
        dt = time.time() - t0
        print(f"mcts-decode: {toks}")
        print(f"{args.max_new} tokens in {dt:.1f}s "
              f"({args.max_new * dcfg.budget} playouts, "
              f"{args.max_new * dcfg.budget / dt:.1f} playouts/s)")
        return

    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=args.max_batch, max_seq=args.max_seq))
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        eng.submit(Request(uid=i,
                           prompt=rng.integers(1, cfg.vocab_size, size=plen),
                           max_new_tokens=args.max_new))
    out = eng.run_until_drained()
    dt = time.time() - t0
    print(f"served {args.requests} requests, {out['tokens']} tokens "
          f"in {dt:.1f}s ({out['tokens']/dt:,.1f} tok/s, "
          f"{out['steps']} engine steps)")
    print(f"latency p50={out['latency_p50']:.3f}s "
          f"p95={out['latency_p95']:.3f}s")
    for k, v in sorted(out["stats"].items()):
        print(f"  {k}={v:.4g}")


if __name__ == "__main__":
    main()
