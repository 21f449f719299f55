#!/usr/bin/env python
"""SweepScope CLI — trace a structure corpus, profile its sweep rounds,
and emit a Chrome-trace/Perfetto file plus the inbound-imbalance table.

For each (structure, grid) case the tool runs the full observed
pipeline on real (host-simulated) devices:

- enables the global span tracer (``repro.obs.trace``) and runs
  ``PSelInvEngine.analyze`` → ``prepare_values`` → ``solve`` so the
  host-side spans (symbolic → plan → lower → compile, factorization,
  dispatch) land in the buffer;
- replays the sweep through ``engine.profile_rounds()`` — the
  per-round segmented re-execution with ``block_until_ready`` fencing —
  joining measured walls against the plan wire tables and the α-β
  simulator;
- writes everything (span lanes, round timeline with per-rank inbound
  bytes, optional serve request lifecycles) to one ``*.trace.json``
  loadable in ``chrome://tracing`` / `ui.perfetto.dev`;
- prints ``RoundProfile.report()`` — the per-round timeline and the
  per-rank inbound bytes/messages/attributed-time skew table,
  cross-checked against PlanLint's static ``load/imbalance`` WARN
  threshold.

Exits non-zero iff any case's measured inbound-byte skew ratio
(max rank / mean rank) exceeds ``--skew-threshold`` (default: the
PlanLint static threshold, ``verify.IMBALANCE_MAX``).

    PYTHONPATH=src python tools/obs_report.py                # nb=16 4x2
    PYTHONPATH=src python tools/obs_report.py --nb 32 --grid 4x2
    PYTHONPATH=src python tools/obs_report.py --chunk 4 --serve 24
    PYTHONPATH=src python tools/obs_report.py -o sweep.trace.json

Runs on a CPU host mesh of ``pr*pc`` devices: unless its environment
already is one, the tool re-execs itself under ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count`` (CPU smoke, not
device speed).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))


def _reexec(ndev: int, argv) -> int:
    from repro.jaxenv import host_mesh_env
    r = subprocess.run([sys.executable, os.path.abspath(__file__)]
                       + list(argv), env=host_mesh_env(ndev), cwd=_ROOT)
    return r.returncode


def _serve_lanes(n: int):
    """Optional serve corpus: push ``n`` mixed-structure requests
    through a worker-threaded SelInvServer (Grid(1,1) — structure
    coalescing, not mesh parallelism) and return the completed request
    objects for the exporter's lifecycle lanes."""
    import scipy.sparse as sp

    from repro.core import sparse
    from repro.core.engine import Grid
    from repro.serve.batcher import BatchWindow
    from repro.serve.server import SelInvServer, ServeConfig

    mats = [sp.csr_matrix(sparse.laplacian_2d(nx, 4) +
                          sp.eye(nx * 4) * 0.1) for nx in (8, 12)]
    cfg = ServeConfig(b=4, grid=Grid(1, 1),
                      window=BatchWindow(max_batch=8, max_wait_ms=2.0))
    with SelInvServer(cfg) as srv:
        reqs = [srv.submit(mats[i % len(mats)]) for i in range(n)]
        srv.drain(timeout=120.0)
        for r in reqs:
            r.result(timeout=120.0)
        return srv.recent_requests()


def run_case(nb: int, pr: int, pc: int, *, chunk: int, reps: int,
             serve: int, out: str, skew_threshold: float) -> int:
    import scipy.sparse as sp

    import jax

    from repro.core import sparse
    from repro.core.engine import Grid, PSelInvEngine
    from repro.obs.export import write_trace
    from repro.obs.trace import TRACER

    TRACER.enable()
    A = sp.csr_matrix(sparse.laplacian_2d(nb, 8))
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(pr, pc))
    vals = eng.prepare_values(A)
    jax.block_until_ready(eng.solve(vals))     # warm + span-recorded

    profile = eng.profile_rounds(vals, chunk=chunk, reps=reps)
    requests = _serve_lanes(serve) if serve else None
    TRACER.disable()

    write_trace(out, spans=TRACER.spans(), profile=profile,
                requests=requests)
    with open(out) as f:
        nev = len(json.load(f)["traceEvents"])
    print(f"[obs-report] laplacian_2d({nb},8) b=8 grid {pr}x{pc}: "
          f"{len(TRACER.spans())} span(s), {profile.nrounds} round(s)"
          + (f", {len(requests)} request(s)" if requests else ""))
    print(f"[obs-report] wrote {out} ({nev} trace events)")
    print()
    print(profile.report())

    skew = profile.skew()
    ratio = skew["skew_ratio"]
    if ratio > skew_threshold:
        print(f"[obs-report] FAIL: measured inbound-byte skew "
              f"{ratio:.2f}x exceeds threshold {skew_threshold:.2f}x")
        return 1
    print(f"[obs-report] OK: measured inbound-byte skew {ratio:.2f}x "
          f"<= threshold {skew_threshold:.2f}x")
    return 0


def main(argv=None) -> int:
    from repro.core.verify import IMBALANCE_MAX

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nb", type=int, default=16,
                    help="supernode grid size: laplacian_2d(nb, 8) at "
                         "b=8 (default 16)")
    ap.add_argument("--grid", default="4x2",
                    help="PRxPC process grid (default 4x2)")
    ap.add_argument("--chunk", type=int, default=1,
                    help="rounds per jitted replay segment (default 1)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed replay passes, per-segment min kept "
                         "(default 3)")
    ap.add_argument("--serve", type=int, default=0,
                    help="additionally run N requests through a "
                         "SelInvServer and export their lifecycle "
                         "lanes (default 0 = skip)")
    ap.add_argument("-o", "--out", default="selinv.trace.json",
                    help="output trace path (default selinv.trace.json)")
    ap.add_argument("--skew-threshold", type=float, default=IMBALANCE_MAX,
                    help="fail when measured max/mean inbound-byte skew "
                         "exceeds this ratio (default: PlanLint's "
                         f"static IMBALANCE_MAX = {IMBALANCE_MAX})")
    args = ap.parse_args(argv)
    pr, pc = (int(x) for x in args.grid.lower().split("x"))

    from repro.jaxenv import in_host_mesh
    if not in_host_mesh(pr * pc):
        return _reexec(pr * pc, sys.argv[1:])

    return run_case(args.nb, pr, pc, chunk=args.chunk, reps=args.reps,
                    serve=args.serve, out=args.out,
                    skew_threshold=args.skew_threshold)


if __name__ == "__main__":
    raise SystemExit(main())
