#!/usr/bin/env python
"""HloLint CLI — run the compiled-artifact verifier
(``core/hlo_verify.py``) over a generated structure corpus: every
shipped executor lowering (overlapped, gated stream under both
``axis_factored`` settings) is traced and lowered on an abstract
mesh and its jaxpr / StableHLO layers are cross-checked against the
plan tables — permute conformance, loop trip counts, wire-byte
conservation, hot-path hygiene. No physical devices are needed (the
8×4 corpus case lints on a single-CPU host):

    PYTHONPATH=src python tools/hlo_lint.py             # default corpus
    PYTHONPATH=src python tools/hlo_lint.py --grid 8x4 --nb 32
    PYTHONPATH=src python tools/hlo_lint.py --compile   # + optimized HLO
    PYTHONPATH=src python tools/hlo_lint.py -v          # per-case report

``--compile`` additionally runs a real XLA compile per case and lints
the optimized HLO (the program XLA actually runs) — the XLA_FLAGS
assignment below provisions enough host devices for every corpus grid
and MUST stay before any other import (jax locks the device count at
first init).

Exits non-zero iff any case produces an ERROR-severity diagnostic —
the CI contract "every lowered program passes PlanLint AND HloLint".
"""
from __future__ import annotations

import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=32")

import argparse                                                # noqa: E402
import sys                                                     # noqa: E402
import time                                                    # noqa: E402

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import scipy.sparse as sp_mod                                  # noqa: E402

from repro.core import hlo_verify, sparse                      # noqa: E402
from repro.core.plan import PlanOptions                        # noqa: E402
from repro.core.pselinv_dist import build_program              # noqa: E402
from repro.core.symbolic import symbolic_factorize             # noqa: E402

#: default corpus: (nx, ny, nb, pr, pc) — the shipped plan shapes,
#: same as ``tools/plan_lint.py``
DEFAULT_CORPUS = [
    (16, 8, 16, 4, 2),
    (32, 8, 32, 4, 2),
    (32, 8, 32, 8, 4),
]

#: the executor lowerings every case lints at the compiled layer
EXECUTORS = [
    ("overlap", PlanOptions()),
    ("stream", PlanOptions(stream=True)),
    ("stream(axis_factored=False)",
     PlanOptions(stream=True, axis_factored=False)),
]


def pad_to_grid(nb: int, pr: int, pc: int) -> int:
    from repro.core.pselinv_dist import pad_nb
    return pad_nb(nb, pr, pc)


def lint_case(nx: int, ny: int, nb: int, pr: int, pc: int, *,
              compile: bool = False, verbose: bool = False):
    """HloLint every executor lowering of one (structure, grid) case.
    Returns (n_errors, n_warnings, n_programs)."""
    bs = symbolic_factorize(
        sp_mod.csr_matrix(sparse.laplacian_2d(nx, ny)), max_supernode=8)
    nbp = pad_to_grid(bs.nsuper, pr, pc)
    nerr = nwarn = 0
    case = f"laplacian_2d({nx},{ny}) nb={nbp} grid {pr}x{pc}"
    for what, opts in EXECUTORS:
        prog = build_program(bs, nbp, 8, pr, pc, options=opts)
        diags = hlo_verify.lint_program(prog, compile=compile)
        errs = [d for d in diags if d.severity == "error"]
        warns = [d for d in diags if d.severity == "warn"]
        nerr += len(errs)
        nwarn += len(warns)
        if errs or warns or verbose:
            print(f"  {case} :: {what}: "
                  f"{len(errs)} error(s), {len(warns)} warning(s)")
        for d in errs + warns:
            print(f"    {d}")
    return nerr, nwarn, len(EXECUTORS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", default=None,
                    help="lint one PRxPC grid (e.g. 8x4) instead of the "
                         "default corpus")
    ap.add_argument("--nb", type=int, default=32,
                    help="supernode blocking for --grid (default 32)")
    ap.add_argument("--compile", action="store_true",
                    help="additionally XLA-compile each program and "
                         "lint the optimized HLO (needs pr*pc devices)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="report clean programs too")
    args = ap.parse_args(argv)

    if args.grid:
        pr, pc = (int(x) for x in args.grid.lower().split("x"))
        corpus = [(args.nb, 8, args.nb, pr, pc)]
    else:
        corpus = DEFAULT_CORPUS

    t0 = time.time()
    nerr = nwarn = nprog = 0
    for (nx, ny, nb, pr, pc) in corpus:
        e, w, p = lint_case(nx, ny, nb, pr, pc, compile=args.compile,
                            verbose=args.verbose)
        nerr += e
        nwarn += w
        nprog += p
    status = "FAIL" if nerr else "OK"
    print(f"[hlo-lint] {status}: {nprog} compiled program(s) across "
          f"{len(corpus)} case(s) — {nerr} error(s), {nwarn} warning(s) "
          f"in {time.time() - t0:.1f}s")
    return 1 if nerr else 0


if __name__ == "__main__":
    raise SystemExit(main())
