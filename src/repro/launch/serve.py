"""Serving launcher: dual-precision engine over a trained/initialized model.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 16 --rate 4 [--policy dual|fp16|fp8]
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="shared prefix tokens prepended to every request "
                         "(exercises COW prefix caching)")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--speculate", action="store_true",
                    help="n-gram speculative decoding (greedy outputs are "
                         "bit-exact vs off; summary gains spec_* fields)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", default="dual",
                    choices=["dual", "fp16", "fp8"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_arch
    from repro.core.policy import DualPrecisionController, SLOConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.models.convert import serving_memory_bytes, to_serving
    from repro.serving.engine import Engine, Request

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt:
        from repro.checkpoint import io
        restored, _ = io.restore(args.ckpt, {"params": params})
        params = restored["params"]
    sparams = to_serving(params)
    mem = serving_memory_bytes(sparams)
    print(f"serving params: {mem['total_bytes']/2**20:.1f} MiB "
          f"({mem['nested_bytes']/max(mem['total_bytes'],1)*100:.0f}% nested)")

    controller = None
    forced = None
    if args.policy == "dual":
        controller = DualPrecisionController(
            SLOConfig(), fp16_ms_per_token=0.5, fp8_ms_per_token=0.25)
    else:
        forced = args.policy

    eng = Engine(cfg, sparams, n_slots=args.slots, capacity=args.capacity,
                 controller=controller, forced_mode=forced,
                 prefix_cache=not args.no_prefix_cache,
                 speculate=args.speculate or None)
    rng = np.random.RandomState(args.seed)
    sys_prompt = list(rng.randint(1, cfg.vocab_size,
                                  args.system_prompt_len))
    for i in range(args.requests):
        plen = max(4, int(rng.normal(args.prompt_len, 4)))
        eng.submit(Request(f"r{i}",
                           sys_prompt + list(rng.randint(1, cfg.vocab_size,
                                                         plen)),
                           max_new=args.max_new))
    fin = eng.run()
    n_tokens = sum(len(r.output) for r in fin)
    modes = [m for r in fin for m in r.modes]
    ps = eng.prefix_cache_stats()
    print(json.dumps({
        "finished": len(fin), "tokens": n_tokens,
        "iterations": eng.iteration,
        "fp16_fraction": modes.count("fp16") / max(len(modes), 1),
        "prefix_hit_rate": round(ps["hit_rate"], 3),
        "blocks_saved": ps["blocks_saved"],
        "window_reclaimed_blocks": eng.stats["window_reclaimed_blocks"],
        **({"spec_acceptance_rate":
                round(eng.spec_stats()["acceptance_rate"], 3),
            "spec_tokens_per_dispatch":
                round(eng.spec_stats()["tokens_accepted_per_dispatch"], 3)}
           if args.speculate else {}),
    }))
    return 0 if len(fin) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
