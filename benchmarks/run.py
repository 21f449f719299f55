"""Benchmark driver — one module per paper table/figure plus kernel and
system microbenches. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json PATH]

``--full`` uses paper-scale matrices (minutes); default sizes finish in
~2-4 minutes on one CPU core. ``--json BENCH_pselinv.json`` additionally
writes every row ({name, us_per_call, derived}) as JSON so the perf
trajectory is machine-readable across PRs.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all rows as JSON (e.g. BENCH_pselinv.json)")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig5,fig8,fig9,kernels,"
                         "selinv,treecomm")
    args = ap.parse_args()

    from repro.jaxenv import enable_compile_cache
    enable_compile_cache()

    from . import (fig5_heatmap, fig8_scaling, fig9_ratio, kernels_bench,
                   pselinv_bench, table1_volume, treecomm_bench)

    benches = {
        "table1": table1_volume.run,
        "fig5": fig5_heatmap.run,
        "fig8": fig8_scaling.run,
        "fig9": fig9_ratio.run,
        "kernels": kernels_bench.run,
        "selinv": pselinv_bench.run,
        "treecomm": treecomm_bench.run,
    }
    selected = (args.only.split(",") if args.only else list(benches))

    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        try:
            benches[name](full=args.full)
        except Exception as e:
            traceback.print_exc()
            failed.append((name, repr(e)))
    if args.json:
        import json

        from .common import RESULTS
        with open(args.json, "w") as f:
            json.dump({"benches": RESULTS,
                       "failed": [n for n, _ in failed]}, f, indent=2)
        print(f"[bench] wrote {len(RESULTS)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        for name, err in failed:
            print(f"{name},FAILED,{err}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
