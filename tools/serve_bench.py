#!/usr/bin/env python
"""Serving-layer benchmark: synthetic mixed-structure Poisson traffic
through :class:`repro.serve.SelInvServer`.

Runs the full acceptance harness (``repro.serve.traffic.run_traffic``):
cold pass → one-compile-per-(structure, bucket) conformance off the
engine trace counters → warm timed pass → warm sequential baseline over
the same matrices → f64 identity check — then prints the serving
scorecard. Run it on a real mesh with f64 enabled:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    JAX_ENABLE_X64=1 PYTHONPATH=src \\
        python tools/serve_bench.py --grid 4x2 [--requests 120] \\
            [--structures 2] [--rate 4000] [--burst] [--json out.json]

``benchmarks/pselinv_bench.py`` drives the same harness in-process for
the recorded trajectory rows; this CLI is the standalone knob-turning
entry point.
"""
from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser(
        description="mixed-structure serving benchmark")
    ap.add_argument("--requests", type=int, default=120,
                    help="trace length (acceptance floor: 100)")
    ap.add_argument("--structures", type=int, default=2,
                    help="distinct block structures in the mix (>= 2)")
    ap.add_argument("--rate", type=float, default=4000.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--burst", action="store_true",
                    help="submit with zero gaps instead of Poisson")
    ap.add_argument("--grid", default="1x1",
                    help="process grid PRxPC (e.g. 4x2; needs PR*PC "
                         "devices)")
    ap.add_argument("--b", type=int, default=8, help="supernode width")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--pressure", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=1,
                    help="repeat each timed pass, keep the best wall "
                         "(steadies ratios on shared hosts)")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fail unless coalesced serving beats the "
                         "sequential baseline by this factor")
    ap.add_argument("--json", default=None,
                    help="also dump the full result dict to this path")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.jaxenv import enable_compile_cache
    enable_compile_cache()

    from repro.core.engine import Grid
    from repro.serve.batcher import BatchWindow
    from repro.serve.traffic import run_traffic

    pr, pc = (int(x) for x in args.grid.lower().split("x"))
    if jax.config.jax_enable_x64:
        dtype, tol, check = jnp.float64, 1e-12, True
    else:
        print("[serve-bench] x64 disabled — skipping the f64 identity "
              "check (set JAX_ENABLE_X64=1)", flush=True)
        dtype, tol, check = jnp.float32, 1e-4, True

    res = run_traffic(
        n_requests=args.requests, n_structures=args.structures,
        rate_hz=(None if args.burst else args.rate), seed=args.seed,
        b=args.b, grid=Grid(pr, pc),
        window=BatchWindow(max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           pressure=args.pressure),
        dtype=dtype, check_identity=check, tol=tol, reps=args.reps,
        log=lambda s: print(f"[serve-bench] {s}", flush=True))

    print(f"[serve-bench] {res['n_requests']} requests, "
          f"{res['n_structures']} structures, grid {pr}x{pc}")
    print(f"  serve:    {res['serve_per_matrix_us']:9.1f} us/matrix  "
          f"({res['serve_throughput_rps']:.0f} rps, "
          f"{res['batches']} batches, occupancy "
          f"{res['serve_batch_occupancy']:.2f})")
    print(f"  baseline: {res['baseline_per_matrix_us']:9.1f} us/matrix")
    print(f"  speedup:  {res['speedup']:9.2f}x")
    print(f"  latency:  p50 {res['serve_p50_us']:.0f} us   p95 "
          f"{res['serve_p95_us']:.0f} us   p99 "
          f"{res['serve_p99_us']:.0f} us")
    print(f"  identity: max |serve - unbatched| = "
          f"{res['identity_max_abs']:.2e} (tol {tol:g})")
    print(f"  compiles: "
          + "  ".join(f"{k}: {t} traces / {b} buckets"
                      for k, (t, b) in res["conformance"].items()))

    if args.json:
        with open(args.json, "w") as f:
            json.dump({k: v for k, v in res.items() if k != "stats"},
                      f, indent=1, default=str)
        print(f"[serve-bench] wrote {args.json}")

    if args.min_speedup and res["speedup"] < args.min_speedup:
        print(f"[serve-bench] FAIL: speedup {res['speedup']:.2f}x < "
              f"{args.min_speedup}x", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
