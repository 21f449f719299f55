"""The one HLO / StableHLO / jaxpr parsing code path of the repo.

Three compiled-artifact layers carry the program XLA actually runs, and
two consumers read them: ``launch/dryrun.py`` prices multi-pod
collective traffic off the optimized HLO, and ``core/hlo_verify.py``
(HloLint) cross-checks every compiled sweep against the CommPlan it was
lowered from. Both used to need their own text scraping; this module is
the shared parser so the regexes, the defining-line-vs-operand-use
guard, and the while-loop trip-count propagation exist exactly once.

Layers and what each yields:

* **optimized HLO** (``compiled.as_text()``): named computations with
  ``while(...), condition=%c, body=%b`` edges — trip counts are read
  from the loop-condition constants and propagated through nesting
  (:func:`computation_multipliers`, the dryrun accounting), optionally
  through ``conditional``/``fusion``/``call`` edges too (multiplier
  inherited, needed to reach the gated comm slots the stream executor
  hides two regions deep). :func:`parse_collectives` extracts every
  *defining* collective op with its ``source_target_pairs``, result
  shape/dtype and enclosing-computation multiplier.
* **StableHLO** (``lowered.as_text()``): loops are inline
  ``stablehlo.while`` regions, not named computations — membership is
  tracked by brace depth and trip counts read from the loop-condition
  ``stablehlo.constant``/``compare LT`` idiom the fori_loop lowering
  emits (:func:`parse_collectives` again; it sniffs the dialect).
* **jaxpr** (``traced.jaxpr``): walked structurally, not as text —
  ``ppermute`` equations carry their ``perm`` parameter verbatim, and
  a ``scan``'s ``length`` parameter is the exact trip count the
  fori_loop stream body runs under (:func:`jaxpr_collectives`).

``collective_bytes`` keeps the exact dryrun semantics (while-edge
multipliers only) — ``launch/dryrun.py`` re-exports it unchanged.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

__all__ = [
    "DTYPE_BYTES", "CollectiveOp", "ConvertOp", "JaxprCollective",
    "split_computations", "computation_multipliers", "collective_bytes",
    "parse_collectives", "parse_converts", "host_transfer_lines",
    "jaxpr_collectives", "jaxpr_converts", "is_stablehlo",
]

#: a collective op, synchronous or the ``-start`` half of an async pair
#: (TPU HLO spells collectives as ``*-start``/``*-done``; the ``-done``
#: half moves nothing new and never matches)
_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute)(-start)?\b(?!-)")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

#: the stablehlo op mnemonics of the same five collectives, normalized
#: to the HLO dash spelling so consumers match on one vocabulary
_STABLEHLO_COLL = {
    "all_gather": "all-gather", "all_reduce": "all-reduce",
    "reduce_scatter": "reduce-scatter", "all_to_all": "all-to-all",
    "collective_permute": "collective-permute",
}
_STABLEHLO_COLL_RE = re.compile(
    r"stablehlo\.(all_gather|all_reduce|reduce_scatter|all_to_all"
    r"|collective_permute)\b")


def is_stablehlo(txt: str) -> bool:
    """Dialect sniff: optimized HLO is the classic ``HloModule`` text
    format; anything else is treated as MLIR StableHLO."""
    return not txt.lstrip()[:400].startswith("HloModule")


# ---------------------------------------------------------------------------
# optimized HLO: computations, trip-count multipliers, byte pricing
# ---------------------------------------------------------------------------

def split_computations(txt: str) -> Dict[str, str]:
    """Top-level ``%name (args) -> ty {`` blocks of an HLO module."""
    blocks: Dict[str, list] = {}
    cur = None
    for line in txt.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([^\s(]+)\s*\(", line)
            cur = m.group(1) if m else None
            if cur:
                blocks[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            blocks[cur].append(line)
    return {k: "\n".join(v) for k, v in blocks.items()}


_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([^\s,]+), body=%?([^\s,]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TF_BRANCH_RE = re.compile(
    r"(?:true|false)_computation=%?([^\s,}]+)")
_CALLS_RE = re.compile(r"\b(?:calls|to_apply)=%?([^\s,}]+)")


def computation_multipliers(txt: str, *,
                            through_calls: bool = False) -> Dict[str, int]:
    """Execution-count multiplier per HLO computation: while-loop bodies
    execute trip-count times (xla's cost/temp analyses count them once —
    verified; scan bodies would otherwise be undercounted). Trip count is
    read from the loop-condition constant; nested loops multiply.

    ``through_calls=True`` additionally propagates the parent's
    multiplier through ``conditional`` branch computations and
    ``fusion``/``call`` callee edges (×1 — executed at most once per
    parent execution). HloLint needs this to see the stream executor's
    gated comm slots, which live in conditional branches inside the
    while body; the dryrun byte pricing keeps the historical
    while-edges-only behavior."""
    blocks = split_computations(txt)
    edges = []  # (parent, callee, trip)
    for parent, body_txt in blocks.items():
        for cond, body in _WHILE_RE.findall(body_txt):
            consts = [int(c) for c in _CONST_RE.findall(blocks.get(cond, ""))]
            trip = max(consts) if consts else 1
            edges.append((parent, body, trip))
        if through_calls:
            for line in body_txt.splitlines():
                for blob in _BRANCHES_RE.findall(line):
                    for br in blob.split(","):
                        br = br.strip().lstrip("%")
                        if br:
                            edges.append((parent, br, 1))
                for br in _TF_BRANCH_RE.findall(line):
                    edges.append((parent, br, 1))
                for callee in _CALLS_RE.findall(line):
                    edges.append((parent, callee, 1))

    return _execution_counts(edges, blocks)


def _execution_counts(edges, names) -> Dict[str, int]:
    """Execution count of each computation (or function) in ``names``
    and every callee of ``edges`` ((caller, callee, k): the callee runs
    k times per caller execution). A callee runs once per execution of
    each of its call sites, summed over callers — shared helpers are
    called both inside and outside loops — and a computation nobody
    calls (the entry) runs once."""
    callers: Dict[str, List[Tuple[str, int]]] = {}
    for caller, callee, k in edges:
        callers.setdefault(callee, []).append((caller, k))
    counts: Dict[str, int] = {}

    def count(name: str) -> int:
        if name not in counts:
            sites = callers.get(name)
            counts[name] = (sum(count(c) * k for c, k in sites)
                            if sites else 1)
        return counts[name]

    for name in set(names) | set(callers):
        count(name)
    return counts


def _defining_collective(line: str):
    """``(op, shapes)`` when the HLO ``line`` defines a collective:
    ``op`` in the dash vocabulary and ``shapes`` the (dtype, dims) of
    its result. An async ``-start`` yields its largest buffer alone
    (its tuple also carries the operand and context scalars). None for
    other lines and for lines that only *use* a collective result, e.g.
    ``%add = f32[...] add(... %all-reduce.1)``."""
    if "=" not in line:
        return None
    rhs = line.split("=", 1)[1]
    m = _COLL_RE.search(rhs)
    if m is None or "%" in rhs[:m.start()]:
        return None
    shapes = [(dt, tuple(int(d) for d in dims.split(",") if d))
              for dt, dims in _SHAPE_RE.findall(rhs[:m.start()])
              if dt in DTYPE_BYTES]
    if m.group(2) and shapes:
        shapes = [max(shapes, key=lambda s: math.prod(s[1]))]
    return m.group(1), shapes


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device collective traffic from the optimized HLO: sum of
    result-shape bytes of every collective op, weighted by the execution
    count of its enclosing computation (while-loop bodies × trip count).
    all-gather/all-to-all results count the full gathered buffer — an
    upper bound within (n-1)/n of wire traffic."""
    mult = computation_multipliers(hlo_text)
    blocks = split_computations(hlo_text)
    out: Dict[str, float] = {}
    for name, body in blocks.items():
        k = mult.get(name, 1)
        for line in body.splitlines():
            coll = _defining_collective(line.strip())
            if coll is None:
                continue
            op, shapes = coll
            nbytes = sum(math.prod(dims) * DTYPE_BYTES[dt]
                         for dt, dims in shapes)
            if nbytes:
                out[op] = out.get(op, 0.0) + float(nbytes) * k
    return out


# ---------------------------------------------------------------------------
# collective op extraction (both text dialects)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CollectiveOp:
    """One *defining* collective op of a lowered/compiled program:
    ``op`` in the HLO dash vocabulary, its ``source_target_pairs``
    (collective-permute only, else None), the result tensor dims and
    dtype, the enclosing computation (``""`` for inline StableHLO
    regions), the execution-count ``multiplier`` of that context
    (while trip counts, nesting multiplied), and the 1-based source
    line in the text it was parsed from."""
    op: str
    pairs: Optional[Tuple[Tuple[int, int], ...]]
    dims: Tuple[int, ...]
    dtype: str
    computation: str
    multiplier: int
    line: int


_HLO_PAIRS_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}")
_SH_PAIRS_RE = re.compile(r"source_target_pairs\s*=\s*dense<(.*?)>")
_SH_RESULT_RE = re.compile(r"->\s*tensor<([0-9x]*)([a-z0-9]+)>\s*$")
_SH_TRIP_CONST_RE = re.compile(
    r"stablehlo\.constant dense<(\d+)>\s*:\s*tensor<i(?:32|64)>")


def _parse_hlo_pairs(line: str) -> Optional[Tuple[Tuple[int, int], ...]]:
    m = _HLO_PAIRS_RE.search(line)
    if not m:
        return None
    body = m.group(1) + "}"          # restore the inner closing brace
    return tuple((int(a), int(b)) for a, b in
                 re.findall(r"\{(\d+),\s*(\d+)\}", body))


def _parse_sh_pairs(line: str) -> Optional[Tuple[Tuple[int, int], ...]]:
    m = _SH_PAIRS_RE.search(line)
    if not m:
        return None
    return tuple((int(a), int(b)) for a, b in
                 re.findall(r"\[(\d+),\s*(\d+)\]", m.group(1)))


def _parse_sh_result(line: str) -> Tuple[Tuple[int, ...], str]:
    m = _SH_RESULT_RE.search(line.rstrip())
    if not m:
        return (), ""
    dims = tuple(int(d) for d in m.group(1).split("x") if d)
    return dims, m.group(2)


def _parse_hlo_collectives(txt: str, *, through_calls: bool
                           ) -> List[CollectiveOp]:
    mult = computation_multipliers(txt, through_calls=through_calls)
    out: List[CollectiveOp] = []
    cur = None
    for lineno, line in enumerate(txt.splitlines(), 1):
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([^\s(]+)\s*\(", line)
            cur = m.group(1) if m else None
            continue
        if line.startswith("}"):
            cur = None
            continue
        ls = line.strip()
        coll = _defining_collective(ls)
        if coll is None:
            continue
        op, shapes = coll
        dtype, dims = shapes[0] if shapes else ("", ())
        out.append(CollectiveOp(
            op=op, pairs=_parse_hlo_pairs(ls) if
            op == "collective-permute" else None,
            dims=dims, dtype=dtype, computation=cur or "",
            multiplier=mult.get(cur or "", 1), line=lineno))
    return out


_SH_FUNC_RE = re.compile(r"func\.func\s+(?:[a-z]+\s+)?@([\w$.\-]+)")
_SH_CALL_RE = re.compile(r"(?:func\.call|call)\s+@([\w$.\-]+)")


def _parse_sh_collectives(txt: str) -> List[CollectiveOp]:
    """StableHLO: loops are inline ``stablehlo.while`` regions, but a
    region body is often just a ``func.call`` to an out-of-line
    ``func.func`` (the fori_loop lowering does exactly this) — so loop
    membership needs both brace-depth region tracking *and* call-graph
    multiplier propagation. A while's trip count is the loop-condition
    integer constant (the ``i < steps`` idiom) found before the
    condition region's ``compare``."""
    lines = txt.splitlines()
    # pass 1: per-function local loop context — collect collective ops
    # and call edges with the *local* multiplier at their site
    ops: List[Tuple[str, CollectiveOp]] = []    # (func, op @ local mult)
    edges: List[Tuple[str, str, int]] = []      # (caller, callee, mult)
    func = ""
    depth = 0
    loops: List[Tuple[int, int]] = []           # (entry_depth, trip)
    pending_while = None
    for lineno, line in enumerate(lines, 1):
        fm = _SH_FUNC_RE.search(line)
        if fm:
            func = fm.group(1)
            loops, pending_while = [], None
        if "stablehlo.while" in line:
            trip = 1
            for look in lines[lineno:lineno + 20]:
                c = _SH_TRIP_CONST_RE.search(look)
                if c:
                    trip = int(c.group(1))
                if "stablehlo.compare" in look:
                    break
            pending_while = (depth, trip)
        local = 1
        for _, t in loops:
            local *= t
        cm = _SH_CALL_RE.search(line)
        if cm:
            edges.append((func, cm.group(1), local))
        m = _STABLEHLO_COLL_RE.search(line)
        if m:
            dims, dtype = _parse_sh_result(line)
            op = _STABLEHLO_COLL[m.group(1)]
            ops.append((func, CollectiveOp(
                op=op, pairs=_parse_sh_pairs(line) if
                op == "collective-permute" else None,
                dims=dims, dtype=dtype, computation=func,
                multiplier=local, line=lineno)))
        depth += line.count("{") - line.count("}")
        if pending_while is not None and depth > pending_while[0]:
            loops.append(pending_while)
            pending_while = None
        while loops and depth <= loops[-1][0]:
            loops.pop()
    # pass 2: propagate function execution counts through call edges
    fmult = _execution_counts(edges, {f for f, _ in ops})
    return [CollectiveOp(op=c.op, pairs=c.pairs, dims=c.dims,
                         dtype=c.dtype, computation=c.computation,
                         multiplier=c.multiplier * fmult[f],
                         line=c.line)
            for f, c in ops]


def parse_collectives(txt: str, *, through_calls: bool = True
                      ) -> List[CollectiveOp]:
    """Every defining collective op of an HLO or StableHLO module text,
    with source-target pairs, result shape and loop-context multiplier
    (dialect auto-detected). ``through_calls`` (HLO dialect only)
    extends trip-count propagation through conditional/fusion/call
    edges so ops inside gated branches inherit the loop multiplier."""
    if is_stablehlo(txt):
        return _parse_sh_collectives(txt)
    return _parse_hlo_collectives(txt, through_calls=through_calls)


# ---------------------------------------------------------------------------
# converts and host transfers (hygiene inputs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvertOp:
    """One dtype conversion: operand dtype → result dtype."""
    src: str
    dst: str
    line: int


_SH_CONVERT_RE = re.compile(
    r"stablehlo\.convert\b.*\(tensor<(?:[0-9x]*)([a-z0-9]+)>\)\s*->"
    r"\s*tensor<(?:[0-9x]*)([a-z0-9]+)>")
_HLO_CONVERT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[[0-9,]*\][^ ]*\s+convert\(\s*([a-z0-9]+)\[")


def parse_converts(txt: str) -> List[ConvertOp]:
    """Every dtype-convert op of an HLO or StableHLO module text."""
    out: List[ConvertOp] = []
    sh = is_stablehlo(txt)
    for lineno, line in enumerate(txt.splitlines(), 1):
        ls = line.strip()
        if sh:
            m = _SH_CONVERT_RE.search(ls)
            if m:
                out.append(ConvertOp(src=m.group(1), dst=m.group(2),
                                     line=lineno))
        else:
            m = _HLO_CONVERT_RE.search(ls)
            if m:
                out.append(ConvertOp(src=m.group(2), dst=m.group(1),
                                     line=lineno))
    return out


#: op / custom-call markers that move data off the device on the hot
#: path (the ``@Sharding`` annotation custom-calls are benign and
#: excluded)
_HOST_XFER_RE = re.compile(
    r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(|"
    r"custom[-_]call.*(?:MoveToHost|MoveToDevice"
    r"|annotate_device_placement)|"
    r"stablehlo\.(infeed|outfeed|send|recv)\b")


def host_transfer_lines(txt: str) -> List[Tuple[int, str]]:
    """(line number, stripped line) of every host-transfer op."""
    out = []
    for lineno, line in enumerate(txt.splitlines(), 1):
        ls = line.strip()
        if "=" not in ls and "stablehlo" not in ls:
            continue
        if _HOST_XFER_RE.search(ls):
            out.append((lineno, ls))
    return out


# ---------------------------------------------------------------------------
# jaxpr layer: structural walk (no text parsing)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class JaxprCollective:
    """One collective equation of a traced program: the primitive name,
    its ``perm`` parameter (ppermute only), and the product of enclosing
    loop trip counts (``scan`` lengths; an unbounded ``while``
    contributes ``None`` → trip is None)."""
    prim: str
    perm: Optional[Tuple[Tuple[int, int], ...]]
    trip: Optional[int]


_COLLECTIVE_PRIMS = {
    "ppermute", "pshuffle", "psum", "pmax", "pmin", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter",
}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for sub in vals:
            if hasattr(sub, "eqns"):               # raw Jaxpr
                yield sub
            elif hasattr(sub, "jaxpr") and hasattr(
                    getattr(sub, "jaxpr"), "eqns"):  # ClosedJaxpr
                yield sub.jaxpr


def jaxpr_collectives(closed_jaxpr) -> List[JaxprCollective]:
    """Walk a ``ClosedJaxpr`` structurally and return every collective
    equation with its loop-trip context. A ``scan``'s exact trip count
    is its ``length`` parameter; a ``while``'s is unknowable statically
    (trip → None)."""
    out: List[JaxprCollective] = []

    def walk(jaxpr, trip):
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            if nm in _COLLECTIVE_PRIMS:
                perm = eqn.params.get("perm")
                out.append(JaxprCollective(
                    prim=nm,
                    perm=tuple((int(s), int(d)) for s, d in perm)
                    if perm is not None else None,
                    trip=trip))
            sub_trip = trip
            if nm == "scan":
                n = int(eqn.params.get("length", 1))
                sub_trip = None if trip is None else trip * n
            elif nm == "while":
                sub_trip = None
            for sub in _sub_jaxprs(eqn):
                walk(sub, sub_trip)

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    walk(jaxpr, 1)
    return out


def jaxpr_converts(closed_jaxpr, src: str = "float64",
                   dst: str = "float32") -> int:
    """Count ``convert_element_type`` equations narrowing ``src`` →
    ``dst`` anywhere in a traced program (the silent-precision-loss
    hygiene input)."""
    count = 0

    def walk(jaxpr):
        nonlocal count
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type":
                try:
                    s = str(eqn.invars[0].aval.dtype)
                    d = str(eqn.params.get("new_dtype", ""))
                except Exception:       # pragma: no cover - exotic avals
                    s = d = ""
                if s == src and d == dst:
                    count += 1
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    walk(jaxpr)
    return count
