#!/usr/bin/env python
"""PlanLint CLI — run the static schedule verifier (``core/verify.py``)
over a generated structure corpus and report every diagnostic.

Lints each (structure, grid) case through every lowering the stack
ships — the CommPlan IR, the overlapped round stream (with and
without a Û liveness window), and the gated
stream tables under both ``axis_factored`` settings — entirely
host-side (no devices needed, an 8×4 corpus lints in seconds):

    PYTHONPATH=src python tools/plan_lint.py            # default corpus
    PYTHONPATH=src python tools/plan_lint.py --grid 8x4 --nb 32
    PYTHONPATH=src python tools/plan_lint.py -v         # per-case report
    PYTHONPATH=src python tools/plan_lint.py --compiled # + HloLint

``--compiled`` chains the HloLint compiled-artifact verifier
(``tools/hlo_lint.py`` / ``core/hlo_verify.py``) over the same corpus:
each executor lowering is traced on an abstract mesh and its jaxpr /
StableHLO layers cross-checked against the plan tables — still no
devices required.

Exits non-zero iff any case produces an ERROR-severity diagnostic —
the CI contract "every lowered program passes PlanLint and (with
``--compiled``) HloLint".
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import scipy.sparse as sp_mod                                  # noqa: E402

from repro.core import sparse, verify                          # noqa: E402
from repro.core.plan import (TreeKind, build_plan,             # noqa: E402
                             schedule_overlapped)
from repro.core.schedule import Grid2D                         # noqa: E402
from repro.core.stream import lower_stream                     # noqa: E402
from repro.core.symbolic import symbolic_factorize             # noqa: E402

#: default corpus: (nx, ny, nb, pr, pc) — the shipped plan shapes (the
#: tier-1 nb=16/32 structures at grid 4×2, plus the 8×4 bigmesh case)
DEFAULT_CORPUS = [
    (16, 8, 16, 4, 2),
    (32, 8, 32, 4, 2),
    (32, 8, 32, 8, 4),
]


def lint_case(nx: int, ny: int, nb: int, pr: int, pc: int, *,
              windows=(None, 1), verbose: bool = False):
    """Lint every lowering of one (structure, grid) case. Returns
    (n_errors, n_warnings, n_artifacts)."""
    bs = symbolic_factorize(
        sp_mod.csr_matrix(sparse.laplacian_2d(nx, ny)), max_supernode=8)
    plan = build_plan(bs, Grid2D(pr, pc), TreeKind.SHIFTED, nb=nb)
    artifacts = [("plan", verify.check_plan(plan))]
    for w in windows:
        ov = schedule_overlapped(plan, window=w)
        artifacts.append((f"overlap(window={w})",
                          verify.check_overlap(ov, plan)))
        for af in (True, False):
            st = lower_stream(ov, axis_factored=af)
            artifacts.append(
                (f"stream(window={w}, axis_factored={af})",
                 verify.check_stream(st, plan)))
    nerr = nwarn = 0
    case = f"laplacian_2d({nx},{ny}) nb={nb} grid {pr}x{pc}"
    for what, diags in artifacts:
        errs = [d for d in diags if d.severity == "error"]
        warns = [d for d in diags if d.severity == "warn"]
        nerr += len(errs)
        nwarn += len(warns)
        if errs or warns or verbose:
            print(f"  {case} :: {what}: "
                  f"{len(errs)} error(s), {len(warns)} warning(s)")
        for d in errs + warns:
            print(f"    {d}")
    return nerr, nwarn, len(artifacts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", default=None,
                    help="lint one PRxPC grid (e.g. 8x4) instead of the "
                         "default corpus")
    ap.add_argument("--nb", type=int, default=32,
                    help="supernode blocking for --grid (default 32)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="report clean artifacts too")
    ap.add_argument("--compiled", action="store_true",
                    help="additionally run the HloLint compiled-"
                         "artifact verifier (tools/hlo_lint.py) over "
                         "the same corpus")
    args = ap.parse_args(argv)

    if args.grid:
        pr, pc = (int(x) for x in args.grid.lower().split("x"))
        corpus = [(args.nb, 8, args.nb, pr, pc)]
    else:
        corpus = DEFAULT_CORPUS

    t0 = time.time()
    nerr = nwarn = narts = 0
    for (nx, ny, nb, pr, pc) in corpus:
        e, w, a = lint_case(nx, ny, nb, pr, pc, verbose=args.verbose)
        nerr += e
        nwarn += w
        narts += a
    status = "FAIL" if nerr else "OK"
    print(f"[plan-lint] {status}: {narts} artifact(s) across "
          f"{len(corpus)} case(s) — {nerr} error(s), {nwarn} warning(s) "
          f"in {time.time() - t0:.1f}s")
    if args.compiled:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import hlo_lint
        ce = cw = cp = 0
        for (nx, ny, nb, pr, pc) in corpus:
            e, w, p = hlo_lint.lint_case(nx, ny, nb, pr, pc,
                                         verbose=args.verbose)
            ce += e
            cw += w
            cp += p
        cstatus = "FAIL" if ce else "OK"
        print(f"[hlo-lint] {cstatus}: {cp} compiled program(s) across "
              f"{len(corpus)} case(s) — {ce} error(s), {cw} warning(s)")
        nerr += ce
    return 1 if nerr else 0


if __name__ == "__main__":
    raise SystemExit(main())
