#!/usr/bin/env python
"""Chip smoke test: the PSelInv main path on a TPU, checked end to end.

    python chip_smoke.py             # one chip: engine + served path
    python chip_smoke.py --chips 4   # the 2x2 process grid only

One process, no children. Phases (each fails the run on its own):

1. device check — platform, kind and count; anything but a TPU exits
   non-zero before any work (there is no CPU fallback);
2. engine solve at N=16,384 (``laplacian_2d(128, 128) + 0.5·I``, b=128,
   grid 1x1) through ``PSelInvEngine.analyze``/``solve``, once with the
   overlapped executor and once with ``PlanOptions(stream=True)``; the
   compiled sweep must contain the Pallas GEMM (``tpu_custom_call``) and
   every struct-present block of three block-columns must match an
   independent f64 ``scipy.sparse.linalg.splu`` solve to a relative
   Frobenius error of at most 1e-4;
3. served path — 8 requests over two structures (N=4,096 and 8,192)
   through a background ``SelInvServer``, twice: every request SOLVED
   within the same tolerance, and the warm pass retraces nothing.

``--chips 4`` runs phase 2 on grid 2x2 over four chips instead (both
executors), additionally asserting that the output spans 4 devices and
that HloLint (``engine.lint_compiled``) finds no ERROR in the TPU
compile. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the full-size matrix: a 128x128 grid Laplacian, ND-ordered, whose
#: symbolic factorization yields uniform b=128 supernodes (N=16,384)
NX, NY, B = 128, 128, 128
#: the served structures (N=4,096 and 8,192; uniform at b=128 too)
SERVE_NX = (32, 64)
SERVE_REQUESTS = 8
SHIFT = 0.5
TOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _ref_columns(A, Ks):
    """Columns of A⁻¹ for supernodes ``Ks`` (width B each) from an f64
    sparse LU — independent of the code under test."""
    import numpy as np
    from scipy.sparse.linalg import splu

    lu = splu(A.tocsc().astype(np.float64))
    n = A.shape[0]
    out = {}
    for K in Ks:
        E = np.zeros((n, B))
        E[K * B:(K + 1) * B] = np.eye(B)
        out[K] = lu.solve(E)
    return out


def _max_rel_err(out_shards, eng, A) -> float:
    """Max over the first, a middle and the last supernode of the
    relative Frobenius error of every struct-present block of that
    block-column, against :func:`_ref_columns`."""
    import numpy as np
    from repro.core.pselinv_dist import gather_blocks

    bs = eng.bs
    ns = bs.nsuper
    Ks = sorted({0, ns // 2, ns - 1})
    ref = _ref_columns(A, Ks)
    blocks = gather_blocks(np.asarray(out_shards), eng)
    err = 0.0
    for K in Ks:
        rows = {K} | {int(i) for i in bs.struct[K]} | {
            I for I in range(ns) if K in set(int(j) for j in bs.struct[I])}
        got = np.concatenate([blocks[I, K] for I in sorted(rows)])
        want = np.concatenate([ref[K][I * B:(I + 1) * B]
                               for I in sorted(rows)])
        err = max(err, float(np.linalg.norm(got - want)
                             / np.linalg.norm(want)))
    return err


def _matrix(nx: int, ny: int):
    import scipy.sparse as sp
    from repro.core import sparse

    A = sparse.laplacian_2d(nx, ny)
    return (A + SHIFT * sp.identity(A.shape[0], format="csr")).tocsr()


def engine_phase(grid, devices, *, lint: bool) -> None:
    """Phase 2 (and the ``--chips 4`` phase): analyze → host prep →
    transfer → compile → solve for both executors on ``grid``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engine import PlanOptions, PSelInvEngine

    A = _matrix(NX, NY)
    vals = None
    for name, opts in (("overlapped", PlanOptions()),
                       ("stream", PlanOptions(stream=True))):
        tag = f"engine {name} {grid.pr}x{grid.pc}"
        t0 = time.perf_counter()
        eng = PSelInvEngine.analyze(A, b=B, grid=grid, options=opts)
        analyze_s = time.perf_counter() - t0
        if vals is None:              # one structure: prep and move once
            t0 = time.perf_counter()
            Lh, Dinv = eng.prepare_values(A, dtype=np.float32)
            prep_s = time.perf_counter() - t0
            shard = NamedSharding(eng.mesh, P("xy"))
            t0 = time.perf_counter()
            vals = (jax.device_put(Lh, shard), jax.device_put(Dinv, shard))
            jax.block_until_ready(vals)
            h2d_s = time.perf_counter() - t0
            del Lh, Dinv
            _log("host", N=A.shape[0], nb=eng.nb, b=B,
                 prep_s=f"{prep_s:.3f}", h2d_s=f"{h2d_s:.3f}")
        t0 = time.perf_counter()
        compiled = eng.jitted().lower(*vals).compile()
        compile_s = time.perf_counter() - t0
        has_kernel = "tpu_custom_call" in compiled.as_text()
        del compiled
        t0 = time.perf_counter()
        out = jax.block_until_ready(eng.solve(vals, dtype=jnp.float32))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(eng.solve(vals, dtype=jnp.float32))
        warm_s = time.perf_counter() - t0
        ndev = len(out.sharding.device_set)
        err = _max_rel_err(out, eng, A)
        st = eng.stats()
        _log(tag, analyze_s=f"{analyze_s:.3f}",
             compile_s=f"{compile_s:.3f}", first_solve_s=f"{first_s:.3f}",
             warm_solve_s=f"{warm_s:.4f}",
             rounds=st["ppermute_rounds"],
             peak_arena_blocks=st["peak_arena_blocks"],
             tpu_custom_call=has_kernel, out_devices=ndev,
             max_rel_err=f"{err:.3e}")
        _log(tag, peak_bytes_in_use=_peak_bytes(devices))
        _check(has_kernel, f"{tag}: no tpu_custom_call in the compiled "
                           "sweep — the Pallas GEMM is not on the path")
        _check(ndev == grid.size, f"{tag}: output spans {ndev} device(s), "
                                  f"expected {grid.size}")
        _check(err <= TOL, f"{tag}: max relative error {err:.3e} > {TOL}")
        if lint:
            diags = eng.lint_compiled()
            errors = [d for d in diags if d.severity == "error"]
            _log(tag, hlolint_errors=len(errors),
                 hlolint_warnings=len(diags) - len(errors))
            for d in errors[:10]:
                print(f"  {d.code}: {d.message}", flush=True)
            _check(not errors, f"{tag}: HloLint found {len(errors)} "
                               "ERROR diagnostic(s) in the TPU compile")
        del out


def serve_phase(devices) -> None:
    """Phase 3: two structures, 8 requests, cold then warm pass through a
    background server; all SOLVED, within tolerance, no warm retrace."""
    import jax.numpy as jnp
    import scipy.sparse as sp

    from repro.core.engine import Grid
    from repro.serve.batcher import BatchWindow, RequestStatus, ServeError
    from repro.serve.server import SelInvServer, ServeConfig
    from repro.serve.traffic import make_trace

    bases = [_matrix(nx, NY) for nx in SERVE_NX]
    trace = make_trace(SERVE_REQUESTS, len(bases), None, seed=0)
    mats = [(bases[t.sidx] + t.shift * sp.identity(
        bases[t.sidx].shape[0], format="csr")).tocsr() for t in trace]
    # batches form only at drain: one per structure, the same buckets in
    # both passes
    cfg = ServeConfig(b=B, grid=Grid(1, 1), dtype=jnp.float32,
                      window=BatchWindow(max_batch=SERVE_REQUESTS,
                                         max_wait_ms=600_000.0))
    with SelInvServer(cfg) as server:
        traces = None
        for pass_name in ("cold", "warm"):
            t0 = time.perf_counter()
            reqs = [server.submit(M) for M in mats]
            server.drain(timeout=900)
            outs = []
            for r in reqs:            # drain returns once batches are
                try:                  # popped; the futures say when done
                    outs.append(r.result(timeout=900))
                except (ServeError, TimeoutError):
                    outs.append(None)  # reported below
            wall = time.perf_counter() - t0
            engines = [server.engine_for(M) for M in bases]
            now = [e.trace_count for e in engines]
            solved = sum(r.status == RequestStatus.SOLVED for r in reqs)
            errs = [_max_rel_err(o, server.engine_for(M), M)
                    for o, M in zip(outs, mats) if o is not None]
            err = max(errs) if errs else float("nan")
            _log(f"serve {pass_name}", requests=len(reqs), solved=solved,
                 wall_s=f"{wall:.3f}", trace_counts=now,
                 max_rel_err=f"{err:.3e}")
            for r in reqs:
                if r.status != RequestStatus.SOLVED:
                    print(f"  request {r.rid}: {r.status.value}: "
                          f"{r.error}", flush=True)
            _check(solved == len(reqs),
                   f"serve {pass_name}: {solved}/{len(reqs)} SOLVED")
            _check(err <= TOL, f"serve {pass_name}: max relative error "
                               f"{err:.3e} > {TOL}")
            if traces is not None:
                _check(now == traces, f"serve warm pass retraced: trace "
                                      f"counts {traces} -> {now}")
            traces = now
    _log("serve", peak_bytes_in_use=_peak_bytes(devices),
         buckets={k: v["buckets_used"]
                  for k, v in server.stats()["structures"].items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: engine + served path on one chip; 4: the "
                         "2x2-grid engine phase only")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    _log("device", **device)
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{d0.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.jaxenv import enable_compile_cache
    from repro.core.engine import Grid
    _log("compile-cache", dir=enable_compile_cache())

    try:
        if args.chips == 4:
            engine_phase(Grid(2, 2), devices[:4], lint=True)
        else:
            engine_phase(Grid(1, 1), devices[:1], lint=False)
            serve_phase(devices[:1])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
