"""HloLint — compiled-artifact conformance against the CommPlan.

PlanLint (``core/verify.py``) proves the *lowered tables* sound; this
module closes the remaining gap: the traced jaxpr / StableHLO /
optimized HLO that XLA actually compiles could still drift from those
tables — a packing bug that survives table construction, a gating bug
in the fori_loop body, or a JAX upgrade that re-lowers
``ppermute``/``lax.cond`` differently would ship silently-wrong or
silently-slow collectives. HloLint parses each compiled layer through
``core/hlo_ir.py`` into a small op graph and cross-checks it against
the :class:`~.pselinv_dist.PSelInvProgram` it was built from, emitting
the same typed :class:`~.verify.PlanDiagnostic` records.

Check families (stable codes):

* **collective conformance** — every compiled ``collective-permute``'s
  source-target pairs must match a plan round (the overlapped executor)
  or a gated comm slot (stream; inside the fori_loop body, with the loop's
  trip count): a pair set no plan entry owns is ``hlo/perm-unknown``
  (a retargeted or foreign permute), a plan entry no compiled op
  matches is ``hlo/perm-missing`` (a dropped round/slot), and a
  matched op whose loop-context multiplier disagrees with the plan's
  trip count is ``hlo/loop-trip``.
* **compiled byte conservation** — compiled wire blocks (pairs × payload
  width × slot activations) must equal the plan yardstick
  (``stream.stream_wire_blocks`` / ``overlap_wire_blocks``) and
  ``simulator.executed_wire_bytes`` (``hlo/bytes-drift``) — the
  compiled corner of the simulated == executed == compiled triangle.
* **hot-path hygiene** — any all-gather/all-reduce/reduce-scatter/
  all-to-all in a program whose whole design is point-to-point rounds
  is ``hlo/stray-collective``; infeed/outfeed/host-placement transfers
  are ``hlo/host-transfer``; a silent f64 → f32 convert on the value
  path is ``hlo/precision-loss``.
* **program-size regression** (WARN) — ``hlo_bytes`` / ``jaxpr_lines``
  more than :data:`SIZE_REGRESS_RATIO` over the recorded
  ``BENCH_pselinv.json`` baseline is ``hlo/size-regress``.

Entry points: :func:`lint_text` (one StableHLO or optimized-HLO text),
:func:`lint_jaxpr` (a traced ``ClosedJaxpr``), and
:func:`lint_program` — which traces and lowers the program's own sweep
on an **abstract mesh** (no devices required: an 8×4 grid lints on a
single-CPU host) and runs every family. ``PSelInvEngine.lint_compiled``
adds the optimized-HLO layer from a real compile, and
``tools/hlo_lint.py`` is the CLI with the same exit-nonzero contract
as ``tools/plan_lint.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import hlo_ir
from .schedule import BYTES_PER_ELT
from .verify import PlanDiagnostic, _err, _warn

__all__ = [
    "HLO_CODES", "SIZE_REGRESS_RATIO", "ExpectedPermute",
    "expected_permutes", "expected_wire_blocks", "compiled_wire_blocks",
    "check_collectives", "check_hygiene", "check_size",
    "lint_text", "lint_jaxpr", "lint_program", "abstract_lower",
    "load_size_baseline",
]

#: every diagnostic code this linter can emit, and what it means
HLO_CODES = {
    "hlo/perm-unknown": "compiled collective-permute whose pair set "
                        "matches no plan round or comm slot",
    "hlo/perm-missing": "plan round / comm slot with no compiled "
                        "collective-permute",
    "hlo/loop-trip": "loop-context execution count disagrees with the "
                     "plan trip count",
    "hlo/bytes-drift": "compiled wire bytes drift from the plan tables "
                       "/ executed wire accounting",
    "hlo/stray-collective": "all-gather/all-reduce/reduce-scatter/"
                            "all-to-all on the point-to-point hot path",
    "hlo/host-transfer": "host transfer op on the hot path",
    "hlo/precision-loss": "silent f64 -> f32 convert on the value path",
    "hlo/size-regress": "compiled program size regressed past the "
                        "recorded baseline (WARN)",
}

#: WARN threshold for the program-size regression lint
SIZE_REGRESS_RATIO = 1.5


# ---------------------------------------------------------------------------
# what the plan says the compiled program must contain
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExpectedPermute:
    """One permute the plan demands of the compiled program: its pair
    set, payload width in (b, b) blocks, the loop trip count of its
    lowering context (1 = unrolled), the number of rounds that actually
    activate it (gated stream slots < trip), and a human label."""
    pairs: frozenset
    width: int
    trip: int
    activations: int
    where: str


def expected_permutes(prog) -> List[ExpectedPermute]:
    """The permute dictionary a compiled sweep of ``prog`` must realize,
    derived from whichever executor lowering the program carries (the
    stream's gated slot tables or the overlapped global rounds)."""
    st = getattr(prog, "stream_tables", None)
    if st is not None:
        out = []
        for si in range(st.nslots):
            perm = st.slot_perm[si]
            if not perm:
                continue
            out.append(ExpectedPermute(
                pairs=frozenset((int(s), int(d)) for s, d in perm),
                width=int(st.slot_width[si]), trip=int(st.steps),
                activations=int(st.slot_active[:, si].sum()),
                where=f"comm slot {si}"))
        return out
    ov = getattr(prog, "overlap_plan", None)
    if ov is not None:
        return [ExpectedPermute(
            pairs=frozenset((int(s), int(d)) for s, d in rnd.perm),
            width=int(rnd.width), trip=1, activations=1,
            where=f"round {t}")
            for t, rnd in enumerate(ov.rounds) if rnd.perm]
    raise ValueError(
        "expected_permutes needs a program with stream_tables or "
        "overlap_plan")


def expected_wire_blocks(prog) -> int:
    """The plan-table wire yardstick in (b, b) blocks: what every
    compiled sweep of ``prog`` must ship (activations × pairs × width
    summed over the permute dictionary). Equals
    ``stream.stream_wire_blocks`` / ``overlap_wire_blocks`` for those
    lowerings by construction."""
    return sum(e.activations * len(e.pairs) * e.width
               for e in expected_permutes(prog))


# ---------------------------------------------------------------------------
# conformance + conservation over parsed collective ops
# ---------------------------------------------------------------------------

def _op_width(op: hlo_ir.CollectiveOp, b: int, batch: int
              ) -> Optional[int]:
    """Payload width of one compiled permute in (b, b) blocks, dividing
    out the trailing block dims and a leading vmapped batch axis.
    ``None`` when the result shape was unparseable."""
    if not op.dims:
        return None
    n = math.prod(op.dims)
    denom = batch * b * b
    if n % denom:
        return -1                     # not a whole number of blocks
    return n // denom


def check_collectives(ops: List[hlo_ir.CollectiveOp], prog, *,
                      batch: int = 1, layer: str = "hlo"
                      ) -> List[PlanDiagnostic]:
    """Collective conformance + compiled byte conservation over the
    parsed op list of one compiled layer."""
    diags: List[PlanDiagnostic] = []
    b = prog.b
    expected = expected_permutes(prog)
    # pool keyed by pair set; exact (pairs, width) matches drain first
    pool: Dict[frozenset, List[ExpectedPermute]] = {}
    for e in expected:
        pool.setdefault(e.pairs, []).append(e)

    compiled_blocks = 0
    cps = [op for op in ops if op.op == "collective-permute"]
    for op in cps:
        pairs = frozenset(op.pairs or ())
        cands = pool.get(pairs)
        if not cands:
            diags.append(_err(
                "hlo/perm-unknown",
                f"{layer} collective-permute (line {op.line}) with pairs "
                f"{sorted(pairs)} matches no plan round or comm slot — "
                "a retargeted or foreign permute",
                round=-1, slot=-1))
            continue
        w = _op_width(op, b, batch)
        exact = [e for e in cands if e.width == w]
        exp = exact[0] if exact else cands[0]
        cands.remove(exp)
        if not cands:
            del pool[pairs]
        if w is not None and w != exp.width:
            diags.append(_err(
                "hlo/bytes-drift",
                f"{layer} collective-permute (line {op.line}) for "
                f"{exp.where} carries {w} block lane(s) "
                f"({'non-integral payload' if w < 0 else 'payload'} "
                f"dims {op.dims}) but the plan packs width "
                f"{exp.width}"))
        if op.multiplier != exp.trip:
            diags.append(_err(
                "hlo/loop-trip",
                f"{layer} collective-permute (line {op.line}) for "
                f"{exp.where} executes x{op.multiplier} but the plan "
                f"runs it under trip count {exp.trip}"))
        compiled_blocks += (exp.activations * len(pairs)
                            * (w if w is not None and w > 0
                               else exp.width))
    for cands in pool.values():
        for e in cands:
            diags.append(_err(
                "hlo/perm-missing",
                f"plan {e.where} (pairs {sorted(e.pairs)}, width "
                f"{e.width}) has no compiled collective-permute in the "
                f"{layer} layer — a dropped round/slot"))

    # conservation: only meaningful when the permute census is complete
    if not any(d.code in ("hlo/perm-unknown", "hlo/perm-missing")
               for d in diags):
        want = expected_wire_blocks(prog)
        if compiled_blocks != want:
            diags.append(_err(
                "hlo/bytes-drift",
                f"{layer} wire volume is {compiled_blocks} blocks "
                f"({compiled_blocks * b * b * BYTES_PER_ELT:.0f} B) but "
                f"the plan tables ship {want} blocks"))
        else:
            from .simulator import executed_wire_bytes
            ex_bytes = executed_wire_bytes(prog)
            if not np.isclose(
                    compiled_blocks * b * b * BYTES_PER_ELT, ex_bytes):
                diags.append(_err(
                    "hlo/bytes-drift",
                    f"{layer} wire volume "
                    f"{compiled_blocks * b * b * BYTES_PER_ELT:.0f} B "
                    f"!= executed_wire_bytes {ex_bytes:.0f} B"))
    return diags


def compiled_wire_blocks(ops: List[hlo_ir.CollectiveOp], prog, *,
                         batch: int = 1) -> int:
    """Wire blocks of one parsed compiled layer, priced with the plan's
    slot activations (gated stream slots execute ``activations`` of
    their ``trip`` rounds) — the compiled corner of the wire triangle."""
    b = prog.b
    expected = expected_permutes(prog)
    pool: Dict[frozenset, List[ExpectedPermute]] = {}
    for e in expected:
        pool.setdefault(e.pairs, []).append(e)
    total = 0
    for op in ops:
        if op.op != "collective-permute":
            continue
        pairs = frozenset(op.pairs or ())
        cands = pool.get(pairs, [])
        w = _op_width(op, b, batch)
        exact = [e for e in cands if e.width == w]
        exp = exact[0] if exact else (cands[0] if cands else None)
        if exp is not None:
            cands.remove(exp)
        act = exp.activations if exp is not None else op.multiplier
        total += act * len(pairs) * (w if w is not None and w > 0
                                     else (exp.width if exp else 0))
    return total


# ---------------------------------------------------------------------------
# hygiene + size regression
# ---------------------------------------------------------------------------

def check_hygiene(txt: str, *, layer: str = "hlo"
                  ) -> List[PlanDiagnostic]:
    """Stray collectives, host transfers, and silent f64 → f32 value
    converts in one compiled text layer."""
    diags: List[PlanDiagnostic] = []
    for op in hlo_ir.parse_collectives(txt):
        if op.op != "collective-permute":
            diags.append(_err(
                "hlo/stray-collective",
                f"{layer} {op.op} (line {op.line}) on the hot path — "
                "every collective of this schedule lowers to "
                "point-to-point collective-permute rounds"))
    for lineno, line in hlo_ir.host_transfer_lines(txt):
        diags.append(_err(
            "hlo/host-transfer",
            f"{layer} host transfer (line {lineno}): {line[:80]}"))
    for cv in hlo_ir.parse_converts(txt):
        if cv.src == "f64" and cv.dst == "f32":
            diags.append(_err(
                "hlo/precision-loss",
                f"{layer} silent f64 -> f32 convert (line {cv.line}) "
                "on the value path"))
    return diags


def load_size_baseline(path: str = "BENCH_pselinv.json", *,
                       stream: bool = True) -> Optional[Dict[str, float]]:
    """The recorded ``hlo_bytes`` baseline for the nb=16 4×2 f32
    single-matrix shape class, from the latest ``BENCH_pselinv.json``
    entry (``selinv/stream_hlo_bytes`` records the stream program's
    size as its value and the overlapped one in the derived column).
    ``None`` when no baseline is recorded."""
    import json
    import os
    import re
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            hist = json.load(f)
        for entry in reversed(hist):
            for row in entry.get("benches", []):
                if row.get("name") == "selinv/stream_hlo_bytes":
                    if stream:
                        return {"hlo_bytes": float(row["us_per_call"])}
                    m = re.search(r"overlap_hlo_bytes=(\d+)",
                                  row.get("derived", ""))
                    if m:
                        return {"hlo_bytes": float(m.group(1))}
    except (ValueError, KeyError, OSError):      # corrupt history
        return None
    return None


def check_size(metrics: Dict[str, float],
               baseline: Optional[Dict[str, float]], *,
               ratio: float = SIZE_REGRESS_RATIO
               ) -> List[PlanDiagnostic]:
    """WARN when a compiled program's ``hlo_bytes`` / ``jaxpr_lines``
    regressed more than ``ratio`` × over the recorded baseline."""
    if not baseline:
        return []
    diags: List[PlanDiagnostic] = []
    for key in ("hlo_bytes", "jaxpr_lines"):
        have, want = metrics.get(key), baseline.get(key)
        if have and want and have > ratio * want:
            diags.append(_warn(
                "hlo/size-regress",
                f"compiled {key} = {have:.0f} is "
                f"{have / want:.2f}x the recorded baseline "
                f"({want:.0f}) — program size regression"))
    return diags


# ---------------------------------------------------------------------------
# layer entry points
# ---------------------------------------------------------------------------

def lint_text(txt: str, prog, *, batch: int = 1,
              layer: Optional[str] = None) -> List[PlanDiagnostic]:
    """Full HloLint pass over one compiled text layer (StableHLO or
    optimized HLO, auto-detected): conformance, conservation, hygiene."""
    if layer is None:
        layer = "stablehlo" if hlo_ir.is_stablehlo(txt) else "hlo"
    ops = hlo_ir.parse_collectives(txt)
    return (check_collectives(ops, prog, batch=batch, layer=layer)
            + check_hygiene(txt, layer=layer))


def lint_jaxpr(closed_jaxpr, prog, *, batch: int = 1
               ) -> List[PlanDiagnostic]:
    """HloLint over the traced jaxpr: structural walk (no text) —
    ppermute perm conformance, loop trip counts from ``scan`` lengths,
    stray collective primitives, f64 → f32 value converts."""
    diags: List[PlanDiagnostic] = []
    expected = expected_permutes(prog)
    pool: Dict[frozenset, List[ExpectedPermute]] = {}
    for e in expected:
        pool.setdefault(e.pairs, []).append(e)
    for jc in hlo_ir.jaxpr_collectives(closed_jaxpr):
        if jc.prim != "ppermute":
            diags.append(_err(
                "hlo/stray-collective",
                f"jaxpr {jc.prim} equation on the hot path — every "
                "collective of this schedule lowers to ppermute"))
            continue
        pairs = frozenset(jc.perm or ())
        cands = pool.get(pairs)
        if not cands:
            diags.append(_err(
                "hlo/perm-unknown",
                f"jaxpr ppermute with pairs {sorted(pairs)} matches no "
                "plan round or comm slot"))
            continue
        exp = cands.pop(0)
        if not cands:
            del pool[pairs]
        if jc.trip is not None and jc.trip != exp.trip:
            diags.append(_err(
                "hlo/loop-trip",
                f"jaxpr ppermute for {exp.where} executes x{jc.trip} "
                f"but the plan runs it under trip count {exp.trip}"))
    for cands in pool.values():
        for e in cands:
            diags.append(_err(
                "hlo/perm-missing",
                f"plan {e.where} (pairs {sorted(e.pairs)}) has no "
                "ppermute equation in the traced jaxpr"))
    n64 = hlo_ir.jaxpr_converts(closed_jaxpr)
    if n64:
        diags.append(_err(
            "hlo/precision-loss",
            f"traced jaxpr narrows f64 -> f32 in {n64} "
            "convert_element_type equation(s) on the value path"))
    return diags


# ---------------------------------------------------------------------------
# whole-program lint on an abstract mesh (no devices required)
# ---------------------------------------------------------------------------

def _traced_sweep(prog, *, batched: bool = False, dtype=None,
                  batch_size: int = 1, mesh=None):
    """AOT-trace the program's own sweep (per whichever executor
    lowering it carries) over ``mesh`` — an
    ``jax.sharding.AbstractMesh`` of the right size when None, so no
    physical devices are required. Returns the jax ``Traced`` object
    (``.jaxpr``, ``.lower()``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from .pselinv_dist import make_sweep_overlapped, make_sweep_stream
    if dtype is None:
        dtype = jnp.float32
    mk = (make_sweep_stream if getattr(prog, "stream_tables", None)
          is not None else make_sweep_overlapped)
    P_dev = prog.pr * prog.pc
    if mesh is None:
        mesh = AbstractMesh((P_dev,), ("xy",))
    spec = P(None, "xy") if batched else P("xy")
    fn = shard_map(mk(prog, batched=batched), mesh=mesh,
                   in_specs=(spec, spec), out_specs=spec)
    shape = ((int(batch_size),) if batched else ()) + (
        P_dev, prog.nbr, prog.nbc, prog.b, prog.b)
    sd = jax.ShapeDtypeStruct(shape, dtype)
    return jax.jit(fn).trace(sd, sd)


def abstract_lower(prog, *, batched: bool = False, dtype=None,
                   batch_size: int = 1):
    """Trace + lower the program's own sweep on a
    ``jax.sharding.AbstractMesh`` — no physical devices: an 8×4-grid
    program lints on a single-CPU host (the ``bigmesh``-free compiled
    conformance path). Returns ``(closed_jaxpr, stablehlo_text)``.
    XLA *compilation* still needs real devices — the optimized-HLO
    layer is the engine's job (``PSelInvEngine.lint_compiled``) or
    :func:`lint_program`'s ``compile=True`` with a real mesh."""
    traced = _traced_sweep(prog, batched=batched, dtype=dtype,
                           batch_size=batch_size)
    return traced.jaxpr, traced.lower().as_text()


def lint_program(prog, *, batched: bool = False, dtype=None,
                 batch_size: int = 1,
                 baseline: Optional[Dict[str, float]] = None,
                 compile: bool = False
                 ) -> List[PlanDiagnostic]:
    """HloLint a program end to end without devices: abstract-mesh
    trace + lower, then the jaxpr and StableHLO layer passes (plus the
    size-regression lint when a ``baseline`` is supplied).
    ``compile=True`` additionally runs a real XLA compile on a mesh of
    ``prog.pr * prog.pc`` physical devices (which must exist) and lints
    the optimized HLO too — the full three-layer pass
    ``PSelInvEngine.lint_compiled`` runs for live sessions."""
    mesh = None
    if compile:
        import jax
        import numpy as _np
        from jax.sharding import Mesh
        P_dev = prog.pr * prog.pc
        if len(jax.devices()) < P_dev:
            raise ValueError(
                f"lint_program(compile=True) needs {P_dev} devices for "
                f"the {prog.pr}x{prog.pc} grid, found "
                f"{len(jax.devices())}")
        mesh = Mesh(_np.array(jax.devices()[:P_dev]), ("xy",))
    traced = _traced_sweep(prog, batched=batched, dtype=dtype,
                           batch_size=batch_size, mesh=mesh)
    jaxpr = traced.jaxpr
    lowered = traced.lower()
    sh_text = lowered.as_text()
    batch = int(batch_size) if batched else 1
    diags = (lint_jaxpr(jaxpr, prog, batch=batch)
             + lint_text(sh_text, prog, batch=batch, layer="stablehlo"))
    if compile:
        diags += lint_text(lowered.compile().as_text(), prog,
                           batch=batch, layer="hlo")
    if baseline:
        metrics = {"hlo_bytes": float(len(sh_text)),
                   "jaxpr_lines": float(
                       len(str(jaxpr).splitlines()))}
        diags += check_size(metrics, baseline)
    return diags
