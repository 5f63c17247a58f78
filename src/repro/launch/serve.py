"""Serving CLI: continuous-batching engine (default) or the static-batch
baseline over the same compiled prefill/decode steps.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --slots 4 --requests 16 --rate 20 --max-len 96

``--static`` switches the admission policy to the legacy whole-batch
barrier (all requests of a batch start and finish together) — the baseline
``BENCH_serve.json`` compares against.  The heavy lifting lives in
``repro.serve``; this module only parses flags, builds the trace, and
prints the measured metrics.
"""
from __future__ import annotations

import argparse
import json

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-batch slot capacity")
    ap.add_argument("--max-len", type=int, default=96,
                    help="KV cache length (prompt + generation budget)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--prompt-lens", type=int, nargs="+",
                    default=[16, 32, 48, 64])
    ap.add_argument("--gen-lens", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--static", action="store_true",
                    help="static-batch baseline admission policy")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import registry
    from repro.serve import ServeEngine, make_poisson_trace

    cfg = (registry.smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    if not cfg.is_decoder():
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    need = max(args.prompt_lens) + max(args.gen_lens) - 1
    if need > args.max_len:
        raise SystemExit(
            f"--max-len {args.max_len} too small for prompt+gen {need}")

    from repro.models import init_model

    params = init_model(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, num_slots=args.slots,
                         max_len=args.max_len)
    trace = make_poisson_trace(
        num_requests=args.requests, rate=args.rate,
        prompt_lens=args.prompt_lens, gen_lens=args.gen_lens,
        vocab_size=cfg.vocab_size, seed=args.seed,
    )
    engine.warmup(args.prompt_lens)

    policy = "static" if args.static else "continuous"
    report = engine.run(trace, policy=policy)
    m = report.metrics()
    print(f"# {policy} serving, {args.arch}"
          f"{' (smoke)' if args.smoke else ''}, slots={args.slots}")
    print(json.dumps(m, indent=2))
    sample = report.results[0]
    print("sample token ids:", sample.tokens[:16])


if __name__ == "__main__":
    main()
