"""PlanLint tests (``core/verify.py``): the verifier is itself verified.

(a) clean corpus — every shipped plan shape (nb=16/32, grids 4×2 and
    8×4, overlapped / stream lowerings, both
    ``axis_factored`` settings, windowed and unwindowed Û pools) lints
    with **zero ERROR diagnostics**, entirely host-side;
(b) mutation self-test — each corruption class the checker pipeline
    exists for (stale generation, dropped anti-dependence, flipped slot
    gate, duplicate ppermute destination, byte-count drift) is injected
    into a deep-copied lowered artifact and must be caught with its
    distinct diagnostic code;
(c) wiring — ``PlanOptions(verify=...)`` validates its mode,
    ``build_program`` runs the pass at build time (default "error"),
    ``engine.analyze(..., verify=...)`` overrides per call, and
    ``enforce_verification`` maps modes to raise / warn / no-op;
(d) tooling — ``tools/plan_lint.py`` exits clean on the default corpus
    and ``tools/record_bench.py`` rejects malformed bench rows.
"""
import copy
import importlib.util
import os
import warnings

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import sparse
from repro.core import verify as V
from repro.core.plan import PlanOptions, build_plan, schedule_overlapped
from repro.core.schedule import Grid2D
from repro.core.stream import lower_stream
from repro.core.symbolic import symbolic_factorize
from repro.core.trees import TreeKind

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _structure(nx):
    return symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(nx, 8)), max_supernode=8)


@pytest.fixture(scope="module")
def ov_plan():
    """The mutation target: nb=32 at 4×2 with window=1 — the tightest Û
    pool, so slot recycling (the race detector's whole subject matter)
    actually occurs."""
    plan = build_plan(_structure(32), Grid2D(4, 2), TreeKind.SHIFTED,
                      nb=32)
    ov = schedule_overlapped(plan, window=1)
    return plan, ov


@pytest.fixture(scope="module")
def stream_tables(ov_plan):
    _plan, ov = ov_plan
    return lower_stream(ov)


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


def _codes(diags):
    return {d.code for d in _errors(diags)}


# ---------------------------------------------------------------------------
# (a) every shipped plan shape lints clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nx,nb,pr,pc", [
    (16, 16, 4, 2),
    (32, 32, 4, 2),
    (32, 32, 8, 4),
])
def test_shipped_plans_lint_clean(nx, nb, pr, pc):
    """The acceptance contract: nb=16/32 at grids 4×2 and 8×4, every
    lowering, both axis_factored settings, zero ERROR diagnostics."""
    plan = build_plan(_structure(nx), Grid2D(pr, pc), TreeKind.SHIFTED,
                      nb=nb)
    assert _errors(V.check_plan(plan)) == []
    for window in (None, 1):
        ov = schedule_overlapped(plan, window=window)
        assert _errors(V.check_overlap(ov, plan)) == [], \
            f"overlap window={window}"
        for af in (True, False):
            st = lower_stream(ov, axis_factored=af)
            assert _errors(V.check_stream(st, plan)) == [], \
                f"stream window={window} axis_factored={af}"


# ---------------------------------------------------------------------------
# (b) mutation self-test: each corruption class fires its distinct code
# ---------------------------------------------------------------------------

def _u_writes(ov):
    """(device, slot) -> {generation: [fill rounds]} over the Û region,
    reconstructed exactly as the verifier sees it."""
    u_lo, base_p = ov.n_ainv, ov.levels[0].base_p
    writes = {}
    for t, rnd in enumerate(ov.rounds):
        lane_j = {}
        for (s, d, kind, lv, _nb) in rnd.edges:
            j = lane_j.get((s, d), 0)
            lane_j[(s, d)] = j + 1
            ds = int(rnd.scatter[d, j])
            if kind in ("xfer", "col-bcast") and u_lo <= ds < base_p:
                writes.setdefault((d, ds), {}).setdefault(lv, []).append(t)
    return writes


def test_mutation_stale_generation(ov_plan):
    """Retarget a col-bcast forward's gather lane at a Û slot whose
    latest visible write is a *different* generation — the exact stale
    tenant bug class of PRs 2/3 — and the race detector must name it."""
    plan, ov = ov_plan
    m = copy.deepcopy(ov)
    writes = _u_writes(m)
    mutated = False
    for t, rnd in enumerate(m.rounds):
        lane_j = {}
        for (s, d, kind, lv, _nb) in rnd.edges:
            j = lane_j.get((s, d), 0)
            lane_j[(s, d)] = j + 1
            if kind != "col-bcast" or bool(rnd.glh[s, j]):
                continue
            for (dev, slot), gens in writes.items():
                if dev != s:
                    continue
                prior = [(r, l) for l, rs in gens.items()
                         for r in rs if r < t]
                if not prior:
                    continue
                rmax = max(r for r, _l in prior)
                if lv not in {l for r, l in prior if r == rmax}:
                    rnd.gather[s, j] = slot
                    mutated = True
                    break
            if mutated:
                break
        if mutated:
            break
    assert mutated, "no retargetable col-bcast lane found"
    assert "race/stale-read" in _codes(V.check_overlap(m, plan))


def test_mutation_dropped_anti_dep(ov_plan):
    """Move a recycled slot's earlier tenant's last reader (its scomp
    boundary) past the later tenant's first fill — the WAR anti-dep the
    scheduler is obligated to enforce — and the race detector must flag
    the overlap."""
    plan, ov = ov_plan
    m = copy.deepcopy(ov)
    writes = _u_writes(m)
    recycled = sorted((k, v) for k, v in writes.items() if len(v) > 1)
    assert recycled, "window=1 plan must recycle Û slots"
    (_devslot, gens) = recycled[0]
    order = sorted(gens)
    la, lb = order[0], order[1]
    first_fill = min(gens[lb])
    moved = False
    for t, ops in enumerate(m.compute_at):
        hit = [op for op in ops if op.kind == "scomp" and op.level == la]
        if hit:
            m.compute_at[t] = [op for op in ops if op not in hit]
            dest = min(first_fill + 1, len(m.compute_at) - 1)
            m.compute_at[dest] = m.compute_at[dest] + hit
            moved = True
            break
    assert moved
    assert "race/war-overlap" in _codes(V.check_overlap(m, plan))


def test_mutation_flipped_gate_bit(stream_tables, ov_plan):
    """Flip one slot_active gate bit off: the receive table still routes
    a device onto the slot, so the gate/receive consistency check (the
    same one executed_wire_bytes prices through) must fire."""
    plan, _ov = ov_plan
    m = copy.deepcopy(stream_tables)
    idx = np.argwhere(m.slot_active)
    t, si = map(int, idx[len(idx) // 2])
    m.slot_active[t, si] = False
    assert "gate/active-mismatch" in _codes(V.check_stream(m, plan))
    assert "gate/active-mismatch" in _codes(V.check_stream_gates(m))


def test_mutation_duplicate_ppermute_dst(stream_tables, ov_plan):
    """Double-book one destination inside a comm slot's pair list — no
    longer a permutation, a payload would be dropped on device."""
    plan, _ov = ov_plan
    m = copy.deepcopy(stream_tables)
    si = max(range(m.nslots), key=lambda i: len(m.slot_perm[i]))
    perm = list(m.slot_perm[si])
    assert len(perm) >= 2
    (s0, d0), (s1, _d1) = perm[0], perm[1]
    perm[1] = (s1, d0)
    slot_perm = list(m.slot_perm)
    slot_perm[si] = tuple(perm)
    m.slot_perm = tuple(slot_perm)
    assert "perm/dup-endpoint" in _codes(V.check_stream(m, plan))


def test_mutation_byte_count_drift(ov_plan):
    """Inflate one edge's byte record: the executor tables no longer
    conserve the plan's tree volumes and the unified conservation pass
    must localize the drifting kind/rank."""
    plan, ov = ov_plan
    m = copy.deepcopy(ov)
    mutated = False
    for rnd in m.rounds:
        if rnd.edges:
            s, d, kind, lv, nb_ = rnd.edges[0]
            rnd.edges[0] = (s, d, kind, lv, nb_ * 2 + 64.0)
            mutated = True
            break
    assert mutated
    diags = V.check_overlap(m, plan)
    assert "conserve/bytes-drift" in _codes(diags)
    # and without the plan there is nothing to conserve against
    assert "conserve/bytes-drift" not in _codes(V.check_overlap(m, None))


def test_mutation_in_round_waw(ov_plan):
    """Point two lanes of one round at the same (device, slot): the
    one-writer-per-round invariant the scheduler enforces at build time
    must also be caught statically."""
    plan, ov = ov_plan
    m = copy.deepcopy(ov)
    mutated = False
    for rnd in m.rounds:
        for d in range(m.pr * m.pc):
            real = [j for j in range(rnd.width)
                    if int(rnd.scatter[d, j]) != m.trash]
            if len(real) >= 2:
                rnd.scatter[d, real[1]] = rnd.scatter[d, real[0]]
                mutated = True
                break
        if mutated:
            break
    assert mutated
    assert "race/waw-round" in _codes(V.check_overlap(m, plan))


def test_mutation_codes_are_distinct():
    """The acceptance criterion's five corruption classes map to five
    distinct diagnostic codes."""
    assert len({"race/stale-read", "race/war-overlap",
                "gate/active-mismatch", "perm/dup-endpoint",
                "conserve/bytes-drift"}) == 5


# ---------------------------------------------------------------------------
# (c) wiring: PlanOptions / build_program / engine / enforce
# ---------------------------------------------------------------------------

def test_plan_options_verify_validation():
    for mode in ("error", "warn", "off"):
        assert PlanOptions(verify=mode).verify == mode
    with pytest.raises(ValueError, match="verify"):
        PlanOptions(verify="loud")


def test_enforce_verification_modes():
    diag = V.PlanDiagnostic(code="race/stale-read", severity="error",
                            message="synthetic")
    with pytest.raises(V.PlanVerificationError) as ei:
        V.enforce_verification([diag], mode="error", where="test")
    assert ei.value.diagnostics == [diag]
    assert "race/stale-read" in str(ei.value)
    with pytest.warns(UserWarning, match="PlanLint"):
        V.enforce_verification([diag], mode="warn", where="test")
    assert V.enforce_verification([diag], mode="off") == [diag]
    with pytest.raises(ValueError, match="verify mode"):
        V.enforce_verification([diag], mode="loud")
    # warn-severity diagnostics never raise, even in error mode
    w = V.PlanDiagnostic(code="load/fanin", severity="warn", message="s")
    with pytest.warns(UserWarning):
        V.enforce_verification([w], mode="error", where="test")


def test_build_program_runs_planlint():
    """The tier-1 verify path: build_program lints the default nb=16
    plan at build time in every mode without complaint (the shipped
    plans are clean), and the verify knob round-trips PlanOptions."""
    from repro.core.pselinv_dist import build_program
    bs = _structure(16)
    for mode in ("error", "warn", "off"):
        prog = build_program(
            bs, 16, 8, 4, 2,
            options=PlanOptions(stream=True, verify=mode))
        assert prog.stream_tables is not None
    # and verify_program over the compiled program is clean end to end
    prog = build_program(bs, 16, 8, 4, 2,
                         options=PlanOptions(stream=True))
    assert _errors(V.verify_program(prog)) == []


def test_verify_artifact_dispatch(ov_plan, stream_tables):
    plan, ov = ov_plan
    assert _errors(V.verify_artifact(plan)) == []
    assert _errors(V.verify_artifact(ov, plan)) == []
    assert _errors(V.verify_artifact(stream_tables, plan)) == []
    with pytest.raises(TypeError, match="verify_artifact"):
        V.verify_artifact(object())


def test_build_program_default_is_the_overlapped_executor():
    """``build_program`` with no options builds what every cell runs:
    the overlapped round stream, no other executor lowering, and a
    program PlanLint passes clean."""
    import dataclasses
    from repro.core.pselinv_dist import build_program, pad_nb
    bs = _structure(16)
    prog = build_program(bs, pad_nb(bs.nsuper, 4, 2), 8, 4, 2)
    assert prog.overlap_plan is not None and prog.stream_tables is None
    # the program carries the plan and its two executor lowerings only
    assert {f.name for f in dataclasses.fields(prog)} == {
        "nb", "b", "pr", "pc", "kind", "bs", "plan", "overlap_plan",
        "stream_tables"}
    assert _errors(V.verify_program(prog)) == []


def test_lint_report_format():
    diags = [V.PlanDiagnostic(code="load/fanin", severity="warn",
                              message="skew", device=3, round=7),
             V.PlanDiagnostic(code="race/stale-read", severity="error",
                              message="stale", slot=12, hint="rekey")]
    rep = V.lint_report(diags)
    assert rep.splitlines()[0] == "PlanLint: 1 error(s), 1 warning(s)"
    # errors sort first; locations and hints are embedded
    assert rep.splitlines()[1].startswith("  [ERROR] race/stale-read")
    assert "slot=12" in rep and "rekey" in rep
    assert "dev=3,round=7" in rep


def test_executed_wire_bytes_routes_through_gate_check(stream_tables):
    """The simulator's stream wire pricing now shares the PlanLint gate
    check: a drifted gate table still raises ValueError."""
    import types
    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import stream_wire_bytes
    prog = types.SimpleNamespace(b=8, stream_tables=stream_tables,
                                 overlap_plan=None)
    assert executed_wire_bytes(prog) == stream_wire_bytes(stream_tables, 8)
    m = copy.deepcopy(stream_tables)
    idx = np.argwhere(m.slot_active)
    t, si = map(int, idx[0])
    m.slot_active[t, si] = False
    bad = types.SimpleNamespace(b=8, stream_tables=m, overlap_plan=None)
    with pytest.raises(ValueError, match="gate"):
        executed_wire_bytes(bad)


# ---------------------------------------------------------------------------
# (d) tooling: the CLI linter and the bench recorder's schema check
# ---------------------------------------------------------------------------

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_plan_lint_cli_clean():
    tool = _load_tool("plan_lint")
    assert tool.main(["--grid", "4x2", "--nb", "16"]) == 0


def test_record_bench_row_schema():
    tool = _load_tool("record_bench")
    ok = [{"name": "selinv/x", "us_per_call": 1.0, "derived": {}}]
    tool.validate_rows(ok, where="test")          # clean rows pass
    with pytest.raises(SystemExit, match="name"):
        tool.validate_rows([{"us_per_call": 1.0}], where="test")
    with pytest.raises(SystemExit, match="us_per_call"):
        tool.validate_rows([{"name": "selinv/x", "us_per_call": "fast"}],
                           where="test")
    tool.validate_history([{"rev": "a", "benches": ok, "failed": []},
                           {"rev": "b", "benches": ok, "failed": []}])
    with pytest.raises(SystemExit, match="duplicate"):
        tool.validate_history([{"rev": "a", "benches": ok, "failed": []},
                               {"rev": "a", "benches": ok, "failed": []}])
