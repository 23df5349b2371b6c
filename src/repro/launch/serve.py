"""Serving driver: load/init a (reduced) model and answer batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduce 16
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import get_strategy
from repro.configs.registry import default_strategy, get_config
from repro.launch.train import enable_compile_cache, reduced_config
from repro.models import api
from repro.models.layers import tree_init
from repro.serve.engine import Engine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduce", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced_config(get_config(args.arch), args.reduce)
    st = get_strategy(default_strategy(args.arch))
    params = tree_init(api.param_tree(cfg, st), jax.random.PRNGKey(0))
    eng = Engine(cfg, st, params, batch_slots=args.slots, max_len=args.max_len)
    reqs = [
        Request(prompt=[(7 * i + j) % cfg.vocab_size for j in range(4)],
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    t0 = time.time()
    eng.generate(reqs)
    dt = time.time() - t0
    ntok = sum(len(r.out) for r in reqs)
    print(f"served {len(reqs)} requests, {ntok} tokens in {dt:.1f}s "
          f"({ntok/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print("  prompt", r.prompt, "->", r.out)
    return reqs


if __name__ == "__main__":
    main()
