"""CLI: `python -m consensus_specs_tpu.serve` — run the sustained-load
attestation-verification service harness and print the serve block.

Flags mirror the CST_SERVE_* env knobs (flags win); stdout is one JSON
object (the `"serve"` block), the human summary goes to stderr.
`JAX_PLATFORMS=cpu` runs the whole thing on the host backend."""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m consensus_specs_tpu.serve",
        description="Sustained-load verification service harness "
                    "(deferred-result futures + batching executor).")
    parser.add_argument("--duration", type=float, default=None,
                        help="measured load duration in seconds "
                             "(CST_SERVE_DURATION_S)")
    parser.add_argument("--rate", type=float, default=None,
                        help="arrival-rate multiple of mainnet per-slot "
                             "traffic; <= 0 = closed-loop capacity mode "
                             "(CST_SERVE_RATE)")
    parser.add_argument("--pool", type=int, default=None,
                        help="distinct precomputed statements "
                             "(CST_SERVE_POOL)")
    parser.add_argument("--committee", type=int, default=None,
                        help="keys aggregated per statement "
                             "(CST_SERVE_COMMITTEE)")
    parser.add_argument("--windows", type=int, default=None,
                        help="throughput windows (CST_SERVE_WINDOWS)")
    parser.add_argument("--max-batch", type=int, default=None,
                        help="statements per RLC dispatch "
                             "(CST_SERVE_MAX_BATCH)")
    parser.add_argument("--depth", type=int, default=None,
                        help="in-flight batch pipeline depth "
                             "(CST_SERVE_DEPTH)")
    args = parser.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    from consensus_specs_tpu.utils.jaxtools import enable_compile_cache

    enable_compile_cache()

    from consensus_specs_tpu.serve.loadgen import (
        LoadConfig,
        config_from_env,
        run_load,
    )

    base = config_from_env()
    overrides = {"duration_s": args.duration, "rate": args.rate,
                 "pool": args.pool, "committee": args.committee,
                 "windows": args.windows, "max_batch": args.max_batch,
                 "depth": args.depth}
    # Rebuild through the dataclass so flag overrides pass the same
    # __post_init__ clamps the env path gets (--windows 0 must not
    # divide-by-zero in run_load).
    cfg = LoadConfig(**{f: (v if v is not None else getattr(base, f))
                        for f, v in overrides.items()})

    print(f"serve: {cfg}", file=sys.stderr, flush=True)
    block = run_load(cfg)
    print(json.dumps(block), flush=True)
    print(f"serve: {block['verifies_per_s']} verifies/s "
          f"(steady={block['steady']}), p50 {block['p50_ms']} ms / "
          f"p99 {block['p99_ms']} ms over {block['settled']} settled "
          f"({block['mode']} loop, {block['duration_s']}s)",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
