"""Selected-inversion numeric benchmark: numpy vs jax vs pallas backends
(the supernodal GEMM/TRSM hot spots through the kernel layer), plus the
distributed sweep comparison — the cross-level *overlapped* executor vs
the uniform round-*stream* executor (one ``lax.fori_loop`` body), both
through the ``PSelInvEngine`` session API — on an 8-device host mesh
(re-exec'd in a subprocess so the main process stays single-device):
trace (lower) time, XLA compile time, HLO size, run time, ppermute round
counts, the simulated executed-schedule times, and the peak arena
footprints. The stream section records
``selinv/stream_compile_ms``/``stream_hlo_bytes``/``stream_us_per_call``
plus the grid-factored wire metrics
``selinv/stream_wire_bytes``/``stream_shifts_per_round``, and asserts
the stream program's HLO text is ≤ 0.5× the unrolled overlapped
program's (the whole point: program size independent of the round
count) *and* its gated executed wire bytes are ≤ 2× the unrolled
overlapped executor's (the flat ring of PR 5 paid ~36× here) while
staying bit-identical in the f32 run (≤1e-4 asserted; tests assert
≤1e-12 in f64). The engine section records
multi-matrix batched solve throughput
(``selinv/solve_batched_us_per_matrix_b{1,4,16}``), the speedup of one
batched B=16 solve over sequential one-matrix ``engine.solve`` calls
(asserted ≥5× per matrix, cold analyze excluded), and the engine
structure-cache
hit count. The serve section re-execs the mixed-structure Poisson
traffic harness (``repro.serve.traffic``) with 8 devices + f64 and
records the serving scorecard
(``selinv/serve_{p50_us,throughput_rps,batch_occupancy}``), asserting
coalesced serving ≥5× the sequential per-matrix baseline, exactly one
compile per (structure, bucket), and ≤1e-12 batched-vs-unbatched
identity. The SweepScope section records the tracing tax on the solve
hot path (``selinv/trace_overhead_pct``, asserted ≤2 % — what lets the
spans stay inline in ``engine.solve``) and the measured per-round
timeline statistics off the ``profile_rounds`` segmented replay
(``selinv/round_p95_us``, ``selinv/inbound_skew_ratio`` — the latter
asserted under PlanLint's static imbalance WARN threshold)."""
from __future__ import annotations

import subprocess
import sys
import time

import numpy as np

import jax

from repro.core import sparse
from repro.core.selinv import compare_with_oracle, selected_inverse
from repro.jaxenv import ROOT, host_mesh_env, in_host_mesh

from .common import csv_row, reemit_child_rows, timed


def run(full: bool = False):
    n = 16 if full else 10
    A = sparse.laplacian_2d(n, n)
    for backend in ("numpy", "jax", "pallas"):
        t0 = time.perf_counter()
        Ainv, bs = selected_inverse(A, max_supernode=16, backend=backend)
        dt = time.perf_counter() - t0
        err = compare_with_oracle(Ainv, bs, A)
        csv_row(f"selinv/{backend}", dt * 1e6,
                f"N={A.shape[0]} nsuper={bs.nsuper} err={err:.2e}")
        assert err < 1e-3
    _plan_lint_bench()
    _hlo_lint_bench()
    _run_ir_compare(full)
    _run_serve_bench(full)
    return True


def _plan_lint_bench():
    """PlanLint static-verifier cost + diagnostic counts, host-side (the
    checker pipeline never touches a device). Records the tier-1 4×2
    lint cost (`selinv/plan_lint_ms`) and the 8×4 ``bigmesh`` case
    (`selinv/bigmesh_8x4_lint_ms`) — the first bench row at a >8-device
    grid (ROADMAP: bench, not just validate, bigger grids). Both must
    report zero ERROR diagnostics: every shipped plan passes PlanLint."""
    import scipy.sparse as sp

    from repro.core import verify
    from repro.core.plan import TreeKind, build_plan, schedule_overlapped
    from repro.core.schedule import Grid2D
    from repro.core.stream import lower_stream, stream_wire_blocks
    from repro.core.symbolic import symbolic_factorize

    for name, nx, nb, pr, pc in (("plan_lint_ms", 16, 16, 4, 2),
                                 ("bigmesh_8x4_lint_ms", 32, 32, 8, 4)):
        bs = symbolic_factorize(
            sp.csr_matrix(sparse.laplacian_2d(nx, 8)), max_supernode=8)
        plan = build_plan(bs, Grid2D(pr, pc), TreeKind.SHIFTED, nb=nb)
        ov = schedule_overlapped(plan)
        st = lower_stream(ov)
        t0 = time.perf_counter()
        diags = (verify.check_plan(plan) + verify.check_overlap(ov, plan)
                 + verify.check_stream(st, plan))
        dt = time.perf_counter() - t0
        nerr = sum(1 for d in diags if d.severity == "error")
        nwarn = len(diags) - nerr
        csv_row(f"selinv/{name}", dt * 1e6,
                f"nb={nb} grid={pr}x{pc} errors={nerr} warnings={nwarn} "
                f"rounds={len(ov.rounds)} "
                f"wire_blocks={stream_wire_blocks(st)}")
        assert nerr == 0, verify.lint_report(diags)


def _hlo_lint_bench():
    """HloLint compiled-artifact verifier cost + diagnostic counts,
    host-side (abstract-mesh trace + lower, `core/hlo_verify.py` — no
    devices). Records the tier-1 nb=16 4×2 stream case
    (`selinv/hlo_lint_ms`): trace + lower the sweep and cross-check the
    compiled jaxpr/StableHLO layers against the plan tables. Must
    report zero ERROR diagnostics: every lowered program passes
    PlanLint AND HloLint."""
    import scipy.sparse as sp

    from repro.core import hlo_verify, verify
    from repro.core.plan import PlanOptions
    from repro.core.pselinv_dist import build_program, pad_nb
    from repro.core.symbolic import symbolic_factorize

    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(16, 8)), max_supernode=8)
    prog = build_program(bs, pad_nb(bs.nsuper, 4, 2), 8, 4, 2,
                         options=PlanOptions(stream=True))
    t0 = time.perf_counter()
    diags = hlo_verify.lint_program(prog)
    dt = time.perf_counter() - t0
    nerr = sum(1 for d in diags if d.severity == "error")
    nwarn = len(diags) - nerr
    csv_row("selinv/hlo_lint_ms", dt * 1e6,
            f"nb=16 grid=4x2 errors={nerr} warnings={nwarn} "
            f"permutes={len(hlo_verify.expected_permutes(prog))} "
            f"wire_blocks={hlo_verify.expected_wire_blocks(prog)}")
    assert nerr == 0, verify.lint_report(diags)


def _run_ir_compare(full: bool):
    """Re-exec the sweep comparison with 8 host devices."""
    if in_host_mesh(8):
        return _ir_compare_child(full)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.pselinv_bench", "--ir-compare"]
        + (["--full"] if full else []),
        env=host_mesh_env(8), cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    reemit_child_rows(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])


def _ir_compare_child(full: bool):
    import jax.numpy as jnp

    from repro.core.engine import Grid, PlanOptions, PSelInvEngine
    from repro.core.pselinv_dist import analyze_structure, prepare_values
    from repro.core.trees import TreeKind

    nx = 32 if full else 16          # nb = nx (b=8 supernodes per grid row)
    A = sparse.laplacian_2d(nx, 8)
    b, pr, pc = 8, 4, 2
    bs, nb = analyze_structure(A, b, pr, pc)
    Lh_s, Dinv_s = prepare_values(A, bs, nb, b, pr, pc)
    Lh = jnp.asarray(Lh_s, jnp.float32)
    Dinv = jnp.asarray(Dinv_s, jnp.float32)

    outs = {}
    rounds = {}
    peaks = {}
    engines = {}
    hlo_bytes = {}
    times = {}

    for name in ("overlap", "stream"):
        t0 = time.perf_counter()
        engines[name] = PSelInvEngine.analyze(
            bs, b=b, grid=Grid(pr, pc),
            options=PlanOptions(kind=TreeKind.SHIFTED,
                                stream=(name == "stream")))
        lowered = engines[name].jitted().lower(Lh, Dinv)
        t_trace = time.perf_counter() - t0
        hlo_text = lowered.as_text()
        hlo_lines = len(hlo_text.splitlines())
        hlo_bytes[name] = len(hlo_text)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        times[name] = (t_trace, t_compile)
        out, dt = timed(
            lambda: jax.block_until_ready(compiled(Lh, Dinv)), reps=3)
        outs[name] = np.asarray(out)
        # static schedule metrics + executed-schedule timing, straight
        # off the cached session (no re-lowering, no hand-wired
        # round_schedule_from_* plumbing)
        stats = engines[name].stats()
        rounds[name] = stats["ppermute_rounds"]
        peaks[name] = stats["peak_arena_blocks"]
        sim = engines[name].simulate()
        csv_row(f"selinv/sweep_{name}_simulated", sim.total_time * 1e6,
                f"nb={nb} rounds={rounds[name]} "
                f"peak_arena_blocks={sim.peak_arena_blocks}")
        csv_row(f"selinv/sweep_{name}_trace", t_trace * 1e6,
                f"nb={nb} hlo_lines={hlo_lines}")
        csv_row(f"selinv/sweep_{name}_compile", t_compile * 1e6, f"nb={nb}")
        csv_row(f"selinv/sweep_{name}_trace_compile",
                (t_trace + t_compile) * 1e6, f"nb={nb}")
        csv_row(f"selinv/sweep_{name}_run", dt * 1e6, f"nb={nb}")
        if name == "stream":
            csv_row("selinv/stream_us_per_call", dt * 1e6, f"nb={nb}")
    # the uniform round-stream executor replays the overlapped rounds
    # bit-for-bit (f64 identity asserted in tests; ≤1e-4 in this f32 run)
    err_t = float(abs(outs["stream"] - outs["overlap"]).max())
    csv_row("selinv/sweep_stream_vs_overlap_maxdiff", 0.0,
            f"err={err_t:.2e}")
    assert err_t < 1e-4, err_t
    # ...and its program must be small: trace+compile in one fori_loop
    # body, HLO ≤ 0.5× the unrolled overlapped program's (the stream's
    # point — program size independent of the round count)
    csv_row("selinv/stream_compile_ms",
            sum(times["stream"]) * 1e3,
            f"nb={nb} overlap_ms={sum(times['overlap']) * 1e3:.0f} "
            f"trace_ms={times['stream'][0] * 1e3:.0f}")
    csv_row("selinv/stream_hlo_bytes", float(hlo_bytes["stream"]),
            f"nb={nb} overlap_hlo_bytes={hlo_bytes['overlap']}")
    assert hlo_bytes["stream"] <= 0.5 * hlo_bytes["overlap"], hlo_bytes
    # ...and its wire must be near-unrolled: the grid-factored shift
    # scheduling gates each round to only its active comm slots, so the
    # executed wire bytes (engine stats == simulator accounting) land
    # within 2× of the unrolled overlapped executor's, where the PR-5
    # flat ring shipped every device's lane stack on every shift of
    # every round (~36× unrolled at this grid)
    from repro.core.schedule import BYTES_PER_ELT
    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import overlap_wire_blocks
    st_eng = engines["stream"]
    s_stats = st_eng.stats()
    wire_stream = s_stats["stream_wire_bytes"]
    assert executed_wire_bytes(st_eng) == wire_stream
    wire_unrolled = (overlap_wire_blocks(st_eng.program.overlap_plan)
                     * b * b * BYTES_PER_ELT)
    csv_row("selinv/stream_wire_bytes", wire_stream,
            f"nb={nb} unrolled={wire_unrolled:.0f} "
            f"ratio={wire_stream / wire_unrolled:.2f}")
    csv_row("selinv/stream_shifts_per_round",
            s_stats["stream_shifts_per_round"],
            f"nb={nb} "
            f"nshifts={len(st_eng.program.stream_tables.shifts)}")
    assert wire_stream <= 2.0 * wire_unrolled, (wire_stream,
                                                wire_unrolled)
    csv_row("selinv/sweep_ppermute_rounds", float(rounds["overlap"]),
            f"nb={nb} overlap={rounds['overlap']}")
    csv_row("selinv/sweep_peak_arena_blocks", float(peaks["overlap"]),
            f"nb={nb} overlap={peaks['overlap']}")
    _engine_batched_bench(A, nb, engines["overlap"])
    _obs_bench(engines["overlap"], Lh, Dinv, nb)
    return True


def _obs_bench(eng, Lh, Dinv, nb):
    """SweepScope scorecard: the tracing tax on the solve hot path
    (spans left inline in ``engine.solve`` — the ≤2 % bar is what lets
    them stay there), plus the measured per-round timeline statistics
    from the ``profile_rounds`` segmented replay (p95 round wall and
    the paper's inbound-overload skew, measured rather than simulated)."""
    import numpy as np

    from repro.obs.trace import TRACER

    vals = (Lh, Dinv)

    def hot():
        return jax.block_until_ready(eng.solve(vals))

    # best-of-many on both sides: the overhead is a ratio of two timed
    # passes on a possibly starved host (cf. _engine_batched_bench)
    TRACER.disable()
    _, dt_off = timed(hot, reps=20, best=True)
    TRACER.enable()
    try:
        _, dt_on = timed(hot, reps=20, best=True)
    finally:
        TRACER.disable()
    overhead_pct = max(0.0, (dt_on - dt_off) / dt_off * 100.0)
    csv_row("selinv/trace_overhead_pct", overhead_pct,
            f"nb={nb} off_us={dt_off * 1e6:.1f} on_us={dt_on * 1e6:.1f}")
    assert overhead_pct <= 2.0, (
        f"tracing tax {overhead_pct:.2f}% on the solve hot path "
        f"(bar: 2%) — off {dt_off * 1e6:.1f}us on {dt_on * 1e6:.1f}us")

    prof = eng.profile_rounds(vals, reps=3)
    walls = prof.round_walls_us()
    sk = prof.skew()
    alpha, beta = prof.fit_alpha_beta()
    csv_row("selinv/round_p95_us", float(np.percentile(walls, 95)),
            f"nb={nb} rounds={prof.nrounds} "
            f"median_us={np.percentile(walls, 50):.1f} "
            f"total_us={prof.wall_us:.0f} "
            f"alpha_us={alpha * 1e6:.1f} beta_ns_per_B={beta * 1e9:.2f}")
    csv_row("selinv/inbound_skew_ratio", sk["skew_ratio"],
            f"nb={nb} static_warn>{sk['static_warn_threshold']:.1f} "
            f"exceeded={sk['exceeds_static_warn']} "
            f"max_B={int(max(sk['inbound_bytes']))} "
            f"mean_B={np.mean(sk['inbound_bytes']):.0f}")
    assert not sk["exceeds_static_warn"], sk
    return True


def _run_serve_bench(full: bool):
    """Re-exec the serving-layer traffic bench under f64 (the ≤1e-12
    identity between every batched result and its unbatched solve is
    only meaningful in double precision)."""
    import jax.numpy  # noqa: F401 — force config resolution
    if in_host_mesh(1) and jax.config.jax_enable_x64:
        return _serve_bench_child(full)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.pselinv_bench",
         "--serve-bench"] + (["--full"] if full else []),
        env=host_mesh_env(1, x64=True), cwd=ROOT, capture_output=True,
        text=True, timeout=900)
    reemit_child_rows(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])


def _serve_bench_child(full: bool):
    """Mixed-structure burst traffic through SelInvServer: records the
    serving scorecard (``selinv/serve_p50_us``/
    ``serve_throughput_rps``/``serve_batch_occupancy``) and asserts
    the PR's three acceptance bars — coalesced serving ≥5× the
    per-matrix throughput of sequential single solves over the same
    ≥100-request ≥2-structure trace, exactly one compile per
    (structure, bucket) off the engine trace counters, and every
    batched result within 1e-12 (f64) of its unbatched solve.

    Grid(1, 1) and a burst (saturated) trace keep the asserted ratio
    about *coalescing* rather than the host scheduler: with simulated
    devices and Poisson sleeps, every thread in the box shares one
    core and the measurement swings 2-3× run to run (the Poisson +
    4×2-mesh path stays covered, unasserted-for-throughput, by the
    ``slow``-marked ``test_serve_traffic_acceptance_4x2``)."""
    import jax.numpy as jnp

    from repro.core.engine import Grid
    from repro.serve.batcher import BatchWindow
    from repro.serve.traffic import run_traffic

    n = 200 if full else 120
    # reps=3, best-of: the ≥5× assert below is a ratio of two timed
    # passes (see _engine_batched_bench for the same treatment).
    res = run_traffic(
        n_requests=n, n_structures=3 if full else 2, rate_hz=None,
        seed=0, b=8, grid=Grid(1, 1), window=BatchWindow(),
        dtype=jnp.float64, check_identity=True, tol=1e-12, reps=3)
    occ = res["serve_batch_occupancy"]
    csv_row("selinv/serve_p50_us", res["serve_p50_us"],
            f"n={n} structures={res['n_structures']} "
            f"p95={res['serve_p95_us']:.0f} p99={res['serve_p99_us']:.0f}")
    csv_row("selinv/serve_throughput_rps", res["serve_throughput_rps"],
            f"n={n} per_matrix_us={res['serve_per_matrix_us']:.1f} "
            f"baseline_us={res['baseline_per_matrix_us']:.1f} "
            f"speedup={res['speedup']:.2f}")
    csv_row("selinv/serve_batch_occupancy", occ,
            f"n={n} batches={res['batches']} "
            f"identity={res['identity_max_abs']:.2e}")
    assert res["speedup"] >= 5.0, (
        f"coalesced serving only {res['speedup']:.2f}x the sequential "
        f"baseline (bar: 5x)")
    return True


def _engine_batched_bench(A, nb, eng):
    """Analyze-once / solve-many throughput: batched engine solves at
    B∈{1,4,16} (per-matrix microseconds), the speedup of the batched
    B=16 hot path over sequential one-matrix ``engine.solve`` calls
    (warmed first, so cold analyze/compile is excluded on both sides),
    and the session structure-cache hit count."""
    import jax.numpy as jnp
    from repro.core.engine import PSelInvEngine, stack_values

    vals = eng.prepare_values(A)
    per_matrix = {}
    for B in (1, 4, 16):
        vb = stack_values([vals] * B)
        # best-of-reps: the ≥5× assert below is a ratio of two timings
        # on a possibly starved host (8 simulated devices share the
        # box), and one descheduled rep at mean-of-3 has flipped it
        _, dt = timed(lambda: jax.block_until_ready(
            eng.solve(vb, dtype=jnp.float32)), reps=5, best=True)
        per_matrix[B] = dt / B
        csv_row(f"selinv/solve_batched_us_per_matrix_b{B}",
                dt / B * 1e6, f"nb={nb} B={B}")
    # sequential engine.solve: one matrix per call (the 5× bar is about
    # the per-call host factorization + dispatch the batched path
    # amortizes away)
    _, dt_seq = timed(lambda: np.asarray(eng.solve(A, dtype=jnp.float32)),
                      reps=3, best=True)
    speedup = dt_seq / per_matrix[16]
    csv_row("selinv/engine_batched_speedup", speedup,
            f"nb={nb} B=16 seq_us={dt_seq * 1e6:.1f} "
            f"batched_us={per_matrix[16] * 1e6:.1f}")
    assert speedup >= 5.0, (dt_seq, per_matrix)
    csv_row("selinv/engine_cache_hits", float(PSelInvEngine.cache_hits),
            f"misses={PSelInvEngine.cache_misses}")
    return True


if __name__ == "__main__":
    if "--ir-compare" in sys.argv:
        # _run_ir_compare re-execs with 8 host devices when needed
        _run_ir_compare(full="--full" in sys.argv)
    elif "--serve-bench" in sys.argv:
        # _run_serve_bench re-execs with 8 devices + x64 when needed
        _run_serve_bench(full="--full" in sys.argv)
    else:
        run(full="--full" in sys.argv)
