"""Analyze-once / solve-many with the PSelInvEngine session API.

One symbolic analysis (trees, rounds, tables, jitted sweep) serves an
entire stream of matrices that share a sparsity structure — the serving
pattern the engine exists for. Values move; structure doesn't.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/pselinv_engine.py
"""
import time

import numpy as np
import scipy.sparse as sp

from repro.core import sparse
from repro.core.engine import Grid, PlanOptions, PSelInvEngine
from repro.core.pselinv_dist import gather_blocks
from repro.core.selinv import dense_selinv_oracle


def main():
    A = sparse.laplacian_2d(16, 8)

    # 1. analyze ONCE: symbolic factorization -> CommPlan IR ->
    #    overlapped round schedule -> per-device tables -> jitted sweep.
    #    The session is cached on (structure, b, grid, options).
    t0 = time.perf_counter()
    engine = PSelInvEngine.analyze(
        A, b=8, grid=Grid(4, 2),
        options=PlanOptions(coalesce_max=8))
    stats = engine.stats()
    print(f"analyze: {time.perf_counter() - t0:.2f}s  "
          f"rounds={stats['ppermute_rounds']} "
          f"peak_arena_blocks={stats['peak_arena_blocks']}")

    # a second analyze of the same structure is a cache hit — same
    # engine object, nothing recompiled
    again = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                  options=PlanOptions(coalesce_max=8))
    print(f"re-analyze is cached: {again is engine} "
          f"(hits={PSelInvEngine.cache_hits})")

    # 2. solve MANY: same structure, different values — one batched
    #    vmapped sweep call, no per-matrix retrace or recompile.
    mats = [A + sp.identity(A.shape[0]) * c for c in (0.0, 0.5, 1.0, 2.0)]
    t0 = time.perf_counter()
    outs = np.asarray(engine.solve_many(mats))        # (B, P, ...)
    print(f"solve_many(B={len(mats)}): {time.perf_counter() - t0:.2f}s  "
          f"out shape {outs.shape}  traces={engine.trace_count}")

    # 3. each batch member is a real selected inverse
    for i, M in enumerate(mats):
        ref = dense_selinv_oracle(M)
        blocks = gather_blocks(outs[i], engine)
        K = 0
        err = abs(blocks[K, K] - ref[:8, :8]).max()
        print(f"  matrix {i}: |A^-1(0,0) - oracle| = {err:.2e}")

    # 4. the cached plan also answers timing questions without
    #    re-lowering anything
    sim = engine.simulate()
    print(f"simulated sweep time: {sim.total_time * 1e6:.1f} us "
          f"(comm/comp = {sim.comm_to_comp_ratio():.2f})")

    # 5. the uniform round-stream executor: the SAME overlapped schedule,
    #    replayed from round-indexed tables by one lax.fori_loop body —
    #    identical output, but the program no longer grows with the
    #    round count (compare the compile metrics via stats(compile=True))
    streng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                   options=PlanOptions(stream=True))
    out_stream = np.asarray(streng.solve(A))
    out_base = np.asarray(engine.solve(A))
    cs, cu = streng.stats(compile=True), engine.stats(compile=True)
    print(f"stream executor: |out - overlapped| = "
          f"{abs(out_stream - out_base).max():.1e}  "
          f"hlo {cs['hlo_bytes'] / 1e3:.0f}kB vs {cu['hlo_bytes'] / 1e3:.0f}kB  "
          f"trace+compile {cs['trace_lower_ms'] + cs['compile_ms']:.0f}ms "
          f"vs {cu['trace_lower_ms'] + cu['compile_ms']:.0f}ms")

    # 6. the axis-factored stream (default): communication is a static
    #    dictionary of per-(grid-offset, lane-width) comm slots over the
    #    (pr, pc) torus, and each fori_loop round lax.cond-gates only the
    #    slots it actually uses — so the stream's executed wire bytes sit
    #    near the overlapped executor's instead of shipping every device's
    #    lane stack on every ring shift of every round.
    #    stats() reports both wire metrics; axis_factored=False recovers
    #    the old flat-ring encoding for an A/B, and shift_budget=k
    #    coarsens the slot dictionary (fewer gated permutes, more wire).
    ss = streng.stats()
    flat = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                 options=PlanOptions(stream=True,
                                                     axis_factored=False))
    fs = flat.stats()
    out_flat = np.asarray(flat.solve(A))
    print(f"axis-factored stream: wire {ss['stream_wire_bytes'] / 1e6:.1f}MB "
          f"vs flat-ring {fs['stream_wire_bytes'] / 1e6:.1f}MB  "
          f"active shifts/round {ss['stream_shifts_per_round']:.2f} "
          f"vs {fs['stream_shifts_per_round']:.2f}  "
          f"|out - flat| = {abs(out_stream - out_flat).max():.1e}")

    # 7. PlanLint: every program above already passed the static
    #    verifier at build time (PlanOptions(verify="error") is the
    #    default — analyze raises PlanVerificationError on any
    #    ERROR-severity diagnostic). Corrupt a copy of the lowered
    #    stream tables the way a buggy scheduler would — flip one
    #    slot_active gate bit off while the receive table still routes a
    #    device onto the slot — and the linter names the defect:
    import copy

    from repro.core import verify

    st = copy.deepcopy(streng.program.stream_tables)
    t, si = np.argwhere(st.slot_active)[0]
    st.slot_active[t, si] = False
    diags = verify.check_stream(st, streng.program.plan)
    print("PlanLint on a corrupted copy:")
    print(verify.lint_report(diags))

    # 8. HloLint: PlanLint proves the *tables* sound; HloLint closes the
    #    last gap by parsing the jaxpr / StableHLO / optimized HLO that
    #    XLA actually compiles and cross-checking it against those same
    #    tables — permute pair sets, fori_loop trip counts, wire-byte
    #    conservation, hot-path hygiene. lint_compiled() runs all three
    #    layers on the live session (PlanOptions(verify_compiled=...)
    #    wires the same pass into build_program; tools/hlo_lint.py is
    #    the CLI over the whole corpus, devices not required).
    from repro.core import hlo_verify

    hdiags = streng.lint_compiled()
    nerr = sum(1 for d in hdiags if d.severity == "error")
    print(f"HloLint over the compiled stream sweep: {nerr} error(s) "
          f"across jaxpr + stablehlo + optimized-hlo layers")

    #    inject the defect HloLint exists for — a stray all-gather on a
    #    hot path whose whole design is point-to-point permute rounds —
    #    into a copy of the lowered StableHLO and it names the line:
    _, sh_text = hlo_verify.abstract_lower(streng.program)
    bad = sh_text.replace(
        "func.func public @main",
        '"stablehlo.all_gather"(%bad) : (tensor<8x8xf32>) -> '
        "tensor<8x8xf32>\nfunc.func public @main", 1)
    hdiags = hlo_verify.check_hygiene(bad, layer="stablehlo")
    print("HloLint on a corrupted copy:")
    print(verify.lint_report(hdiags))

    # 9. SweepScope: the lints above verify the schedule; the
    #    observability tier *measures* it. Enable the global span
    #    tracer, re-run analyze + solve, and every host-side stage
    #    (symbolic -> plan -> lower -> verify, value prep, solve
    #    dispatch) lands in a ring buffer; profile_rounds() then
    #    re-executes the sweep as per-round jitted segments with
    #    block_until_ready fencing — the measured per-round timeline,
    #    joined against the plan wire tables and the alpha-beta
    #    simulator. Everything exports to one Chrome-trace JSON
    #    (chrome://tracing, ui.perfetto.dev); tools/obs_report.py is
    #    the CLI over the same pipeline.
    from repro.obs.export import write_trace
    from repro.obs.trace import TRACER

    TRACER.enable()
    obs_eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                    options=PlanOptions(coalesce_max=6))
    vals = obs_eng.prepare_values(A)
    obs_eng.solve(vals)
    spans = TRACER.spans()
    print(f"traced {len(spans)} host spans: "
          + " ".join(sorted({s.name for s in spans})))

    profile = obs_eng.profile_rounds(vals, reps=2)
    TRACER.disable()
    print(profile.report())          # per-round walls + imbalance table
    path = write_trace("pselinv_engine.trace.json", spans=spans,
                       profile=profile)
    print(f"wrote {path} — load it in chrome://tracing or "
          f"ui.perfetto.dev")


if __name__ == "__main__":
    main()
