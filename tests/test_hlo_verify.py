"""HloLint tests (``core/hlo_verify.py`` + ``core/hlo_ir.py``): the
compiled-artifact verifier is itself verified.

(a) clean corpus — every shipped executor lowering (overlapped /
    gated stream under both ``axis_factored`` settings,
    single and vmapped-batched) traces, lowers and lints with **zero
    ERROR diagnostics** at the jaxpr and StableHLO layers — on an
    abstract mesh, so the 8×4 bigmesh case runs without devices;
(b) mutation self-test — each corruption class the linter exists for
    (retargeted permute pair, dropped round/slot, stray all-gather,
    silent f64 → f32 convert, payload byte drift, loop-trip tampering)
    is injected into a copied compiled artifact and must be caught
    with its distinct diagnostic code;
(c) wire triangle — compiled blocks parsed back out of the StableHLO
    equal the plan-table yardstick and ``executed_wire_bytes`` for both
    the overlapped and stream lowerings;
(d) parser — the shared ``hlo_ir`` multiplier propagation
    (while-edges-only for the dryrun pricing, through-calls for the
    linter) on a synthetic HLO module, and the size-regression lint;
(e) wiring + tooling — ``PlanOptions(verify_compiled=...)`` validates
    its mode, ``build_program`` runs the pass at build time,
    ``engine.compile_stats``/``lint_compiled`` report and lint the
    optimized HLO on real devices, and ``tools/hlo_lint.py`` exits
    clean on the nb=16 corpus.
"""
import dataclasses
import importlib.util
import os
import re

import pytest
import scipy.sparse as sp

from conftest import run_sub
from repro.core import hlo_ir
from repro.core import hlo_verify as HV
from repro.core import sparse
from repro.core.plan import PlanOptions
from repro.core.pselinv_dist import build_program, pad_nb
from repro.core.schedule import BYTES_PER_ELT
from repro.core.symbolic import symbolic_factorize
from repro.core.verify import PlanVerificationError, enforce_verification

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _structure(nx):
    return symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(nx, 8)), max_supernode=8)


def _program(nx, pr, pc, **opts):
    bs = _structure(nx)
    return build_program(bs, pad_nb(bs.nsuper, pr, pc), 8, pr, pc,
                         options=PlanOptions(**opts))


@pytest.fixture(scope="module")
def stream_prog():
    """The mutation target: the nb=16 4×2 gated stream program."""
    return _program(16, 4, 2, stream=True)


@pytest.fixture(scope="module")
def stream_art(stream_prog):
    """(jaxpr, stablehlo_text) of the stream sweep, lowered once on an
    abstract mesh (no devices)."""
    return HV.abstract_lower(stream_prog)


@pytest.fixture(scope="module")
def ov_prog():
    return _program(16, 4, 2)


@pytest.fixture(scope="module")
def ov_art(ov_prog):
    return HV.abstract_lower(ov_prog)


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


def _codes(diags):
    return {d.code for d in _errors(diags)}


# ---------------------------------------------------------------------------
# (a) every shipped executor lowering lints clean at the compiled layer
# ---------------------------------------------------------------------------

def test_stream_compiled_lints_clean(stream_prog, stream_art):
    jaxpr, sh = stream_art
    assert _errors(HV.lint_jaxpr(jaxpr, stream_prog)) == []
    assert _errors(HV.lint_text(sh, stream_prog)) == []


def test_overlap_compiled_lints_clean(ov_prog, ov_art):
    jaxpr, sh = ov_art
    assert _errors(HV.lint_jaxpr(jaxpr, ov_prog)) == []
    assert _errors(HV.lint_text(sh, ov_prog)) == []


def test_exec_compiled_lints_clean():
    assert _errors(HV.lint_program(_program(16, 4, 2))) == []


def test_stream_unfactored_compiled_lints_clean():
    prog = _program(16, 4, 2, stream=True, axis_factored=False)
    assert _errors(HV.lint_program(prog)) == []


def test_batched_compiled_lints_clean(stream_prog):
    """The vmapped batch axis divides out of the payload widths."""
    diags = HV.lint_program(stream_prog, batched=True, batch_size=4)
    assert _errors(diags) == []


def test_bigmesh_8x4_compiled_lints_without_devices():
    """The acceptance contract: the 8×4 (32-rank) programs lint at the
    compiled layer on this single-device host — AbstractMesh lowering
    needs no physical devices."""
    import jax
    assert jax.device_count() < 32
    for opts in (dict(), dict(stream=True)):
        prog = _program(32, 8, 4, **opts)
        assert _errors(HV.lint_program(prog)) == [], f"opts={opts}"


def test_jaxpr_scan_carries_stream_trip(stream_prog, stream_art):
    """The fori_loop lowers to a jaxpr ``scan`` whose ``length`` is the
    stream's exact trip count — every ppermute inherits it."""
    jaxpr, _ = stream_art
    trips = {jc.trip for jc in hlo_ir.jaxpr_collectives(jaxpr)
             if jc.prim == "ppermute"}
    assert trips == {int(stream_prog.stream_tables.steps)}


# ---------------------------------------------------------------------------
# (b) mutation self-test: every corruption class fires its own code
# ---------------------------------------------------------------------------

def _cp_line_idx(sh):
    idxs = [i for i, ln in enumerate(sh.splitlines())
            if "stablehlo.collective_permute" in ln]
    assert idxs, "no collective_permute in the lowered text"
    return idxs


def test_mutation_retargeted_permute(stream_prog, stream_art):
    """Rewriting one permute's source_target_pairs to a pair set no
    comm slot owns is hlo/perm-unknown."""
    _, sh = stream_art
    lines = sh.splitlines()
    i = _cp_line_idx(sh)[0]
    mut = re.sub(r"source_target_pairs\s*=\s*dense<.*?>",
                 "source_target_pairs = dense<[[0, 0]]>", lines[i])
    assert mut != lines[i]
    lines[i] = mut
    codes = _codes(HV.lint_text("\n".join(lines), stream_prog))
    assert "hlo/perm-unknown" in codes


def test_mutation_dropped_slot(stream_prog, stream_art):
    """Deleting a compiled permute orphans its comm slot:
    hlo/perm-missing (and only that — the rest still match), and
    enforce_verification(mode="error") raises on it."""
    _, sh = stream_art
    lines = sh.splitlines()
    del lines[_cp_line_idx(sh)[0]]
    diags = HV.lint_text("\n".join(lines), stream_prog)
    codes = _codes(diags)
    assert "hlo/perm-missing" in codes
    assert "hlo/perm-unknown" not in codes
    with pytest.raises(PlanVerificationError):
        enforce_verification(diags, mode="error", where="mutated sweep")


def test_mutation_stray_collective(stream_prog, stream_art):
    _, sh = stream_art
    lines = sh.splitlines()
    lines.insert(_cp_line_idx(sh)[0],
                 '    %stray = "stablehlo.all_gather"(%arg0) : '
                 "(tensor<8x8xf32>) -> tensor<8x8xf32>")
    codes = _codes(HV.lint_text("\n".join(lines), stream_prog))
    assert "hlo/stray-collective" in codes


def test_mutation_precision_loss(stream_prog, stream_art):
    _, sh = stream_art
    lines = sh.splitlines()
    lines.insert(_cp_line_idx(sh)[0],
                 "    %narrowed = stablehlo.convert %arg0 : "
                 "(tensor<8x8xf64>) -> tensor<8x8xf32>")
    codes = _codes(HV.lint_text("\n".join(lines), stream_prog))
    assert "hlo/precision-loss" in codes


def test_mutation_byte_drift(stream_prog, stream_art):
    """Editing a permute's result payload to a width no slot packs is
    hlo/bytes-drift."""
    _, sh = stream_art
    lines = sh.splitlines()
    i = _cp_line_idx(sh)[0]
    head, tail = lines[i].rsplit("-> tensor<", 1)
    dims = tail.split("x")
    dims[0] = "999"
    lines[i] = head + "-> tensor<" + "x".join(dims)
    codes = _codes(HV.lint_text("\n".join(lines), stream_prog))
    assert "hlo/bytes-drift" in codes


def test_mutation_loop_trip(stream_prog, stream_art):
    """A permute whose loop-context execution count disagrees with the
    slot's trip count is hlo/loop-trip."""
    _, sh = stream_art
    ops = hlo_ir.parse_collectives(sh)
    cps = [op for op in ops if op.op == "collective-permute"]
    assert cps and all(
        op.multiplier == int(stream_prog.stream_tables.steps)
        for op in cps)
    mut = [dataclasses.replace(op, multiplier=1) if i == 0 else op
           for i, op in enumerate(ops)]
    codes = _codes(HV.check_collectives(mut, stream_prog,
                                        layer="stablehlo"))
    assert "hlo/loop-trip" in codes


# ---------------------------------------------------------------------------
# (c) the wire triangle: compiled == plan tables == executed
# ---------------------------------------------------------------------------

def test_wire_triangle_stream(stream_prog, stream_art):
    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import stream_wire_blocks
    _, sh = stream_art
    blocks = HV.compiled_wire_blocks(hlo_ir.parse_collectives(sh),
                                     stream_prog)
    assert blocks == HV.expected_wire_blocks(stream_prog)
    assert blocks == stream_wire_blocks(stream_prog.stream_tables)
    b = stream_prog.b
    assert blocks * b * b * BYTES_PER_ELT == \
        executed_wire_bytes(stream_prog)


def test_wire_triangle_overlap(ov_prog, ov_art):
    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import overlap_wire_blocks
    _, sh = ov_art
    blocks = HV.compiled_wire_blocks(hlo_ir.parse_collectives(sh),
                                     ov_prog)
    assert blocks == HV.expected_wire_blocks(ov_prog)
    assert blocks == overlap_wire_blocks(ov_prog.overlap_plan)
    b = ov_prog.b
    assert blocks * b * b * BYTES_PER_ELT == executed_wire_bytes(ov_prog)


# ---------------------------------------------------------------------------
# (d) the shared parser: multiplier propagation + size regression
# ---------------------------------------------------------------------------

_SYNTH_HLO = """\
HloModule synth

%inner (q: f32[2]) -> f32[2] {
  %q = f32[2] parameter(0)
  %cp2 = f32[2] collective-permute(%q), source_target_pairs={{0,1}}
  ROOT %r2 = f32[2] add(%q, %q)
}

%body (p: f32[2]) -> f32[2] {
  %p = f32[2] parameter(0)
  %cp = f32[2] collective-permute(%p), source_target_pairs={{0,1},{1,0}}
  %f = f32[2] fusion(%cp), kind=kLoop, calls=%inner
  ROOT %r = f32[2] add(%cp, %f)
}

%cond (s: f32[2]) -> pred[] {
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%c, %c), direction=LT
}

ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2] parameter(0)
  ROOT %w = f32[2] while(%x), condition=%cond, body=%body
}
"""


def test_hlo_multiplier_propagation():
    """while edges always propagate trip counts; fusion/call edges only
    under through_calls (what HloLint needs to see gated slots)."""
    m = hlo_ir.computation_multipliers(_SYNTH_HLO)
    assert m["body"] == 5 and m["inner"] == 1
    mc = hlo_ir.computation_multipliers(_SYNTH_HLO, through_calls=True)
    assert mc["body"] == 5 and mc["inner"] == 5
    ops = {op.computation: op
           for op in hlo_ir.parse_collectives(_SYNTH_HLO)}
    assert ops["body"].multiplier == 5
    assert ops["inner"].multiplier == 5
    assert ops["body"].pairs == ((0, 1), (1, 0))


_ASYNC_HLO = """\
HloModule async_synth

%body (p: f32[2,8,8]) -> f32[2,8,8] {
  %p = f32[2,8,8]{2,1,0} parameter(0)
  %collective-permute-start.1 = (f32[2,8,8]{2,1,0}, f32[2,8,8]{2,1,0}, u32[], u32[]) collective-permute-start(%p), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.1 = f32[2,8,8]{2,1,0} collective-permute-done(%collective-permute-start.1)
  ROOT %r = f32[2,8,8]{2,1,0} add(%p, %collective-permute-done.1)
}

%cond (s: f32[2,8,8]) -> pred[] {
  %c = s32[] constant(3)
  ROOT %lt = pred[] compare(%c, %c), direction=LT
}

ENTRY %main (x: f32[2,8,8]) -> f32[2,8,8] {
  %x = f32[2,8,8]{2,1,0} parameter(0)
  ROOT %w = f32[2,8,8]{2,1,0} while(%x), condition=%cond, body=%body
}
"""


def test_hlo_async_collective_counted_once():
    """TPU HLO splits a permute into ``-start``/``-done``: the pair is one
    op with the start's pairs and buffer shape, priced once per trip."""
    ops = hlo_ir.parse_collectives(_ASYNC_HLO)
    assert [(op.op, op.pairs, op.dims, op.multiplier) for op in ops] == \
        [("collective-permute", ((0, 1), (1, 0)), (2, 8, 8), 3)]
    assert hlo_ir.collective_bytes(_ASYNC_HLO) == \
        {"collective-permute": 2 * 8 * 8 * 4 * 3}


def test_collective_bytes_keeps_dryrun_semantics():
    """The dryrun pricing stays while-edges-only: the fused permute
    counts once, the loop-body one trip-count times."""
    out = hlo_ir.collective_bytes(_SYNTH_HLO)
    assert out == {"collective-permute": 2 * 4 * 5 + 2 * 4}
    from repro.launch.dryrun import collective_bytes as dryrun_cb
    assert dryrun_cb is hlo_ir.collective_bytes


def test_size_baseline_and_regress(stream_art):
    baseline = HV.load_size_baseline(os.path.join(
        ROOT, "BENCH_pselinv.json"))
    assert baseline is not None and baseline["hlo_bytes"] > 0
    _, sh = stream_art
    ok = HV.check_size({"hlo_bytes": float(len(sh))}, baseline)
    assert [d for d in ok if d.code == "hlo/size-regress"] == []
    bloated = HV.check_size(
        {"hlo_bytes": 2.0 * baseline["hlo_bytes"]}, baseline)
    assert [d.code for d in bloated] == ["hlo/size-regress"]
    assert all(d.severity == "warn" for d in bloated)
    assert HV.check_size({"hlo_bytes": 1.0}, None) == []


# ---------------------------------------------------------------------------
# (e) wiring: options validation, build-time pass, engine reporting
# ---------------------------------------------------------------------------

def test_plan_options_verify_compiled_validates():
    for mode in ("error", "warn", "off"):
        assert PlanOptions(verify_compiled=mode).verify_compiled == mode
    with pytest.raises(ValueError, match="verify_compiled"):
        PlanOptions(verify_compiled="bogus")


def test_build_program_verify_compiled_clean():
    """verify_compiled="error" runs HloLint inside build_program and a
    clean program builds without raising."""
    bs = _structure(16)
    prog = build_program(bs, pad_nb(bs.nsuper, 4, 2), 8, 4, 2,
                         options=PlanOptions(stream=True,
                                             verify_compiled="error"))
    assert prog.stream_tables is not None


def test_engine_compile_stats_and_lint_compiled():
    """On 8 real devices: compile_stats (single and batched) reports
    the optimized-HLO ppermute census and collective bytes, and
    lint_compiled passes all three layers clean."""
    run_sub("""
        import jax
        import scipy.sparse as sp
        from repro.core import hlo_verify, sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine

        assert len(jax.devices()) == 8
        A = sparse.laplacian_2d(16, 8)
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                    options=PlanOptions(stream=True))
        n_exp = len(hlo_verify.expected_permutes(eng.program))
        cs = eng.compile_stats()
        assert cs["ppermute_count"] == n_exp, cs
        assert cs["collective_bytes"] > 0
        csb = eng.compile_stats(batched=True, batch_size=4)
        assert csb["ppermute_count"] == n_exp, csb
        assert csb["collective_bytes"] > cs["collective_bytes"]

        diags = eng.lint_compiled(verify_compiled="error")
        assert [d for d in diags if d.severity == "error"] == []
        assert eng.lint_compiled() is diags  # cached per shape class

        # the override is part of the session cache key
        eng2 = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                     options=PlanOptions(stream=True),
                                     verify_compiled="error")
        assert eng2 is not eng
        assert eng2.options.verify_compiled == "error"
        print("OK", n_exp)
    """)


# ---------------------------------------------------------------------------
# (f) tooling: the HloLint CLI exits clean
# ---------------------------------------------------------------------------

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hlo_lint_cli_clean():
    tool = _load_tool("hlo_lint")
    assert tool.main(["--grid", "4x2", "--nb", "16"]) == 0
