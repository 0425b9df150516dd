"""Serving driver: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --requests 8 --max-new 16 --act-impl ppa
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import init_params, param_specs
from repro.runtime import enable_compile_cache
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mixed-lens", action="store_true",
                    help="vary prompt lengths across requests "
                         "(exercises the length-bucketed coalescer)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-coalesce", action="store_true",
                    help="serial admission: batch=1 prefill per request, "
                         "per-slot sampling (token-identical, slower)")
    ap.add_argument("--act-impl", default=None,
                    choices=[None, "exact", "ppa", "ppa8"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.act_impl:
        cfg = cfg.replace(act_impl=args.act_impl)
    params = init_params(param_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, n_slots=args.slots,
                      cache_len=args.cache_len,
                      coalesce=not args.no_coalesce)

    rng = np.random.default_rng(0)
    lens = ([max(2, args.prompt_len // 2 ** (i % 3)) for i in
             range(args.requests)] if args.mixed_lens
            else [args.prompt_len] * args.requests)
    for rid in range(args.requests):
        extra = {}
        if cfg.enc_layers:
            extra["enc_feats"] = rng.normal(
                0, 0.1, (cfg.enc_seq, cfg.d_model)).astype(np.float32)
        if cfg.vision_tokens:
            extra["vision_embeds"] = rng.normal(
                0, 0.02, (cfg.vision_tokens, cfg.d_model)).astype(np.float32)
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, lens[rid]).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
            extra=extra or None))

    t0 = time.time()
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        steps += 1
        if steps > 10_000:
            raise RuntimeError("scheduler did not drain")
    dt = time.time() - t0
    total_tokens = args.requests * args.max_new
    mode = "serial" if args.no_coalesce else "coalesced"
    print(f"served {args.requests} requests / {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens / dt:.1f} tok/s, {steps} engine steps, "
          f"{mode} admission, {eng.prefill_retraces} prefill trace(s), "
          f"act_impl={cfg.act_impl})")


if __name__ == "__main__":
    main()
