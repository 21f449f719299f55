"""Distributed PSelInv on a JAX device mesh — the executable version of
the paper's algorithm with tree-based restricted collectives.

The selected-inversion sweep (Alg. 1, loop 2) runs as one SPMD program on
a flattened ``pr × pc`` grid ("xy" axis). Since this refactor the sweep
is driven end-to-end by the **CommPlan IR** (`core/plan.py`) — the same
plan object the simulator accounts, so the schedule that is *simulated*
is the schedule that *runs*:

  host plan   ``build_plan``           events → trees → per-edge bytes
  compile     ``schedule_overlapped``  one cross-level stream of coalesced
                                       ppermute rounds → dense per-device
                                       index tables
  device      ``make_sweep_overlapped`` (or ``make_sweep_stream``, the
              same rounds as one ``lax.fori_loop``) — table-driven
              gather / ppermute / scatter over a per-device block arena

Per elimination-tree **level** (all supernodes at equal etree depth are
independent — the paper's pipelining, executed rather than approximated;
level L+1's traffic rides the rounds of level L):

    (a) xfer-in    L̂(I,K) → owner of Û(K,I)        [p2p rounds, batched]
    (b) col-bcast  Û(K,I) down its grid column      [trees, shared rounds]
    (1) one masked block-GEMM for the whole level   [kernels.ops]
    (c) row-reduce partials onto owner of A⁻¹(J,K)  [trees, shared rounds]
    (f) xfer-out   A⁻¹(J,K)ᵀ → A⁻¹(K,J) owner       [p2p, symmetric case]
    (2,3) diagonal update + restricted row reduce

Every comm round is one ``lax.ppermute`` of a stack of (b, b) blocks
with O(1) table lookups (``jnp.take`` + dynamic gather/scatter) — no
per-pair ``jnp.where`` chains — so trace/compile time stays flat in the
number of concurrent collectives.

Symmetric matrices (as the paper's implementation): Û(K,I) = L̂(I,K)ᵀ and
A⁻¹(K,J) = A⁻¹(J,K)ᵀ — both identities hold blockwise for unpivoted LU.
Data is dense-blocked with uniform supernode width ``b`` and explicit
zeros for structurally-zero blocks: numerics are unaffected, while the
*communication* pattern is restricted to the true sparsity structure.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.ops import pselinv_round_gemm
from ..obs.trace import TRACER
from .plan import (CommPlan, OverlappedExec, PlanOptions, build_plan,
                   schedule_overlapped, schedule_stream)
from .stream import (COMP_DIAGW, COMP_GEMM, COMP_NOOP, COMP_SCOMP,
                     COMP_WRITE, StreamTables)
from .symbolic import BlockStructure, symbolic_factorize
from .trees import TreeKind

__all__ = ["PSelInvProgram", "build_program",
           "make_sweep_overlapped", "make_sweep_stream",
           "analyze_structure", "prepare_values", "prepare_values_many",
           "check_values_pattern", "gather_blocks"]


@dataclass
class PSelInvProgram:
    """A compiled sweep: grid geometry, the CommPlan, its overlapped
    round stream and, when asked for, the stream executor's tables."""
    nb: int
    b: int
    pr: int
    pc: int
    kind: TreeKind
    bs: BlockStructure
    plan: Optional[CommPlan] = None
    overlap_plan: Optional[OverlappedExec] = None
    stream_tables: Optional[StreamTables] = None   # uniform round stream

    @property
    def nbr(self) -> int:
        return self.nb // self.pr

    @property
    def nbc(self) -> int:
        return self.nb // self.pc


# ---------------------------------------------------------------------------
# IR path: plan -> overlapped round stream -> tables
# ---------------------------------------------------------------------------

def build_program(bs: BlockStructure, nb: int, b: int, pr: int, pc: int,
                  kind: TreeKind = TreeKind.SHIFTED,
                  coalesce_max: int = 8,
                  window: int | None = None,
                  stream: bool = False, *,
                  options: PlanOptions | None = None,
                  verify: str = "error",
                  verify_compiled: str = "off") -> PSelInvProgram:
    """Build the CommPlan IR and compile it to executable tables.

    ``options`` (a :class:`~.plan.PlanOptions`) bundles and overrides
    the loose ``kind``/``coalesce_max``/``window``/``stream`` kwargs —
    the engine/session API passes the whole bundle through so every
    consumer reads the same knobs.

    The program always carries the cross-level overlapped round stream
    (`plan.schedule_overlapped`) consumed by
    :func:`make_sweep_overlapped`. ``window`` caps the arena's Û pool at
    that many live levels (None = whole sweep resident; see
    ``plan.schedule_overlapped``). ``stream=True`` additionally lowers
    the overlapped rounds into the uniform round-indexed tables of
    ``core/stream.py`` for :func:`make_sweep_stream` — the whole sweep
    as one ``lax.fori_loop`` body.

    ``verify`` (overridden by ``options.verify`` when an options bundle
    is passed) runs the PlanLint static pass (``core/verify.py``) over
    every artifact just compiled: ``"error"`` raises
    :class:`~.verify.PlanVerificationError` on any ERROR-severity
    diagnostic, ``"warn"`` condenses the report into one
    ``warnings.warn``, ``"off"`` skips the pass.

    ``verify_compiled`` (overridden by ``options.verify_compiled``)
    additionally runs the HloLint compiled-artifact pass
    (``core/hlo_verify.py``): the program's own sweep is traced and
    lowered on an abstract mesh (no devices required) and the jaxpr /
    StableHLO layers are cross-checked against the tables just built —
    permute conformance, loop trip counts, wire-byte conservation,
    hot-path hygiene. Same three modes; default ``"off"`` because the
    pass costs a full re-trace + lowering of the sweep."""
    if options is not None:
        kind = options.kind
        coalesce_max, window = options.coalesce_max, options.window
        stream = options.stream
        verify = options.verify
        verify_compiled = options.verify_compiled
    if nb % pr or nb % pc:
        raise ValueError(f"nb={nb} not divisible by grid {pr}x{pc}")
    from ..obs.trace import TRACER
    from .schedule import Grid2D
    with TRACER.span("plan.build", nb=nb):
        plan = build_plan(bs, Grid2D(pr, pc), kind, nb=nb)
    st = None
    with TRACER.span("plan.schedule", stream=stream):
        if stream:
            ov, st = schedule_stream(plan, coalesce_max=coalesce_max,
                                     window=window, options=options)
        else:
            ov = schedule_overlapped(plan, coalesce_max=coalesce_max,
                                     window=window, options=options)
        prog = PSelInvProgram(
            nb=nb, b=b, pr=pr, pc=pc, kind=kind, bs=bs, plan=plan,
            overlap_plan=ov, stream_tables=st)
    if verify != "off":
        from .verify import enforce_verification, verify_program
        with TRACER.span("plan.verify", mode=verify):
            enforce_verification(
                verify_program(prog), mode=verify,
                where=f"build_program(nb={nb}, grid={pr}x{pc}, "
                      f"stream={stream})")
    if verify_compiled != "off":
        from .hlo_verify import lint_program
        from .verify import enforce_verification
        with TRACER.span("plan.verify_compiled", mode=verify_compiled):
            enforce_verification(
                lint_program(prog), mode=verify_compiled,
                where=f"compiled sweep of build_program(nb={nb}, "
                      f"grid={pr}x{pc}, stream={stream})")
    return prog


@contextlib.contextmanager
def _scope(step: str, level: Optional[int] = None):
    """``jax.named_scope`` ``sweep.<step>`` (nesting ``level<k>`` when the
    level is static): a name in the ops' ``op_name`` metadata, so a
    profiler trace puts each device op under the sweep step it belongs
    to. Names only — the compiled program is otherwise unchanged."""
    with jax.named_scope(f"sweep.{step}"):
        if level is None:
            yield
        else:
            with jax.named_scope(f"level{level}"):
                yield


def _gi(buf, i):         # gather rows, bounds statically guaranteed
    return buf.at[i].get(mode="promise_in_bounds")


def _gather_lanes(arena, lh_f, g, lh_m, mixed: bool):
    """Per-lane select between the arena and the resident input L̂ shard
    (no arena copy of L̂ exists). ``mixed`` is the static whole-table
    check — streams/rounds without xfer-in lanes skip the second gather
    entirely; where lanes mix, indices are masked into the untaken
    buffer so both gathers stay in bounds. One definition shared by the
    overlapped and stream executors — the masking trick must never
    drift between them."""
    if not mixed:
        return _gi(arena, g)
    blks = _gi(arena, jnp.where(lh_m, 0, g))
    blks_l = _gi(lh_f, jnp.where(lh_m, g, 0))
    return jnp.where(lh_m[:, None, None], blks_l, blks)


def _wrap_sweep(body, batched: bool):
    """Lift a per-device sweep body into the shard_map calling
    convention. Single-matrix: per-device shards are (1, nbr, nbc, b, b)
    under ``in_specs=P("xy")``. Batched: shards are (B, 1, nbr, nbc, b,
    b) under ``in_specs=P(None, "xy")`` — the leading batch axis is
    vmapped through the *value* tensors only, while the closed-over
    index/mask tables (value-independent by construction) are shared
    across every lane, so a batch of B matrices with one structure costs
    one trace and one compile."""
    if batched:
        def sweep(Lh, Dinv):
            return jax.vmap(body)(Lh[:, 0], Dinv[:, 0])[:, None]
    else:
        def sweep(Lh, Dinv):
            return body(Lh[0], Dinv[0])[None]
    return sweep


# ---------------------------------------------------------------------------
# overlapped path: one global cross-level round stream over a block arena
# ---------------------------------------------------------------------------


# The four arena compute phases of the overlapped sweep — ONE definition
# shared by the unrolled overlapped executor (per-level shapes, static
# tables) and the stream executor (NK-padded shapes, dynamically indexed
# tables): the delta-add and masking tricks below are the bit-identity
# contract between the two and must never drift. Each helper derives the
# supernode count from its table operands, so both shape regimes flow
# through the same code. Each runs under its ``sweep.<kind>`` scope;
# ``level`` (static in the overlapped executor, a loop index in the
# stream one) nests under it where known.

def _phase_gemm(arena, ut, cm, N, nbr, nbc, b, base_p, level=None):
    """Level GEMM: partial[k, i] = Σ_j A⁻¹[i, j] · Û_m[k, j]ᵀ into the
    shared partial region. ``ut`` are the (nk*nbc,) arena addresses of
    the Û lanes (trash where struct-absent — ``cm`` zeroes those)."""
    with _scope("gemm", level):
        nk = ut.shape[0] // nbc
        U = _gi(arena, ut).reshape(nk, nbc, b, b)
        Ainv = lax.slice_in_dim(arena, 0, N).reshape(nbr, nbc, b, b)
        partial = pselinv_round_gemm(Ainv, U, cm)
        return lax.dynamic_update_slice(
            arena, partial.reshape(nk * nbr, b, b), (base_p, 0, 0))


def _phase_write(arena, kcs, wr, wc, N, nbr, nbc, b, base_p, level=None):
    """A⁻¹(C, K) column write for every K of the level: masked delta +
    scatter-add — same-level K's write disjoint (device, slot) pairs, so
    duplicate ``kcs`` entries add zeros."""
    with _scope("write", level):
        nk = kcs.shape[0]
        partial = lax.slice_in_dim(
            arena, base_p, base_p + nk * nbr).reshape(nk, nbr, b, b)
        w = jnp.transpose(wr * wc[:, None])            # (nbr, nk)
        Ainv = lax.slice_in_dim(arena, 0, N).reshape(nbr, nbc, b, b)
        old = Ainv.at[:, kcs].get(mode="promise_in_bounds")
        new = -jnp.swapaxes(partial, 0, 1)             # (nbr, nk, b, b)
        Ainv = Ainv.at[:, kcs].add(w[:, :, None, None] * (new - old),
                                   mode="promise_in_bounds")
        return lax.dynamic_update_slice(
            arena, Ainv.reshape(N, b, b), (0, 0, 0))


def _phase_scomp(arena, ut, cm, krs, rm, N, nbr, nbc, b, base_s,
                 level=None):
    """Diagonal partial sum S(K) = Σ_I A⁻¹(K, I) · L̂(I, K) into the
    shared S region (masked to row K%pr by ``rm``)."""
    with _scope("scomp", level):
        nk = krs.shape[0]
        Uh_m = _gi(arena, ut).reshape(nk, nbc, b, b) * cm[:, :, None, None]
        Ainv = lax.slice_in_dim(arena, 0, N).reshape(nbr, nbc, b, b)
        Arow = _gi(Ainv, krs)
        S = jnp.einsum("kjab,kjcb->kac", Arow * cm[:, :, None, None],
                       Uh_m, precision=lax.Precision.HIGHEST)
        return lax.dynamic_update_slice(
            arena, S * rm[:, None, None], (base_s, 0, 0))


def _phase_diagw(arena, Dinv_f, slots, root, idx, N, base_s, dtype,
                 level=None):
    """Diagonal write A⁻¹(K,K) = D⁻¹ − Sᵀ at the owner. ``slots`` may be
    padded with the trash block (stream path): those lanes carry a
    no-device root (mask 0) and the D⁻¹ gather clamps them in-bounds —
    an identity for the real, always-< N, slots."""
    with _scope("diagw", level):
        nk = slots.shape[0]
        S = lax.slice_in_dim(arena, base_s, base_s + nk)
        m = (root == idx).astype(dtype)
        newd = (_gi(Dinv_f, jnp.minimum(slots, N - 1))
                - jnp.swapaxes(S, -1, -2))
        return arena.at[slots].add(
            m[:, None, None] * (newd - _gi(arena, slots)),
            mode="promise_in_bounds")

# The overlapped per-device body, factored into module-level pieces so
# the normal executor (`make_sweep_overlapped`) and the profiling replay
# (`make_sweep_segments`, driven by ``obs.rounds``) run the *same* code:
# the replay is the sweep cut at jit boundaries, not a re-implementation,
# so its per-round timings measure exactly what the fused sweep executes.

def _overlap_init(ov, b, Dinv_f, idx, dtype):
    """Fresh arena + structless-supernode diagonal seeds (leaves without
    fill + grid padding get A⁻¹(K,K) = D⁻¹ up front)."""
    with _scope("init"):
        arena = jnp.zeros((ov.arena_blocks, b, b), dtype=dtype)
        if len(ov.diag_set_root):
            slots = jnp.asarray(ov.diag_set_slot)
            m = (jnp.asarray(ov.diag_set_root) == idx).astype(dtype)
            arena = arena.at[slots].add(
                m[:, None, None] * _gi(Dinv_f, slots),
                mode="promise_in_bounds")
    return arena


def _overlap_compute(ov, op, arena, Dinv_f, idx, r, c, b, dtype):
    """One scheduled compute op at a round boundary. Numerics live in
    the shared ``_phase_*`` helpers (one definition with the stream
    executor); this just feeds them the level's static tables. The
    per-device Û gather table maps the dense (k, j) lane grid onto the
    compact recycled pool slots (trash lanes are struct-masked before
    use)."""
    N, nbr, nbc = ov.n_ainv, ov.nbr, ov.nbc
    lv = ov.levels[op.level]
    cm = jnp.take(jnp.asarray(lv.cmask, dtype=dtype), c, axis=0)
    if op.kind == "gemm":
        ut = jnp.take(jnp.asarray(lv.u_gather), idx, axis=0)
        return _phase_gemm(arena, ut, cm, N, nbr, nbc, b, lv.base_p,
                           op.level)
    if op.kind == "write":
        wr = jnp.take(jnp.asarray(lv.col_write_row, dtype=dtype),
                      r, axis=0)                        # (nk, nbr)
        wc = jnp.take(jnp.asarray(lv.col_write_col, dtype=dtype),
                      c, axis=0)                        # (nk,)
        return _phase_write(arena, jnp.asarray(lv.kcs), wr, wc,
                            N, nbr, nbc, b, lv.base_p, op.level)
    if op.kind == "scomp":
        ut = jnp.take(jnp.asarray(lv.u_gather), idx, axis=0)
        rm = jnp.take(jnp.asarray(lv.diag_rowmask, dtype=dtype),
                      r, axis=0)                        # (nk,)
        return _phase_scomp(arena, ut, cm, jnp.asarray(lv.krs),
                            rm, N, nbr, nbc, b, lv.base_s, op.level)
    # "diagw":  A⁻¹(K,K) = D⁻¹ − (Σ A⁻¹(K,I)L̂(I,K))ᵀ
    return _phase_diagw(arena, Dinv_f, jnp.asarray(lv.diag_slot),
                        jnp.asarray(lv.diag_root), idx, N,
                        lv.base_s, dtype, op.level)


def _overlap_round(ov, t, arena, Lh_f, Dinv_f, idx, r, c, b, dtype):
    """One executed round: the boundary's pinned compute ops, the
    owner-local lane moves, then round ``t``'s coalesced multi-lane
    ppermute with per-lane gather/scatter/accumulate/transpose tables."""
    for op in ov.compute_at[t]:
        arena = _overlap_compute(ov, op, arena, Dinv_f, idx, r, c, b,
                                 dtype)
    rnd = ov.rounds[t]
    if rnd.lwidth:
        with _scope("lanes"):
            lg = jnp.take(jnp.asarray(rnd.lgather), idx, axis=0)
            ls = jnp.take(jnp.asarray(rnd.lscatter), idx, axis=0)
            lt = jnp.take(jnp.asarray(rnd.ltmask), idx, axis=0)
            llh = jnp.take(jnp.asarray(rnd.lglh), idx, axis=0)
            blks = _gather_lanes(arena, Lh_f, lg, llh,
                                 bool(rnd.lglh.any()))
            blks = jnp.where(lt[:, None, None],
                             jnp.swapaxes(blks, -1, -2), blks)
            # non-participating lanes land in the trash block
            arena = arena.at[ls].set(blks, mode="promise_in_bounds")
    if rnd.perm:
        with _scope("permute"):
            g = jnp.take(jnp.asarray(rnd.gather), idx, axis=0)
            s_ = jnp.take(jnp.asarray(rnd.scatter), idx, axis=0)
            am = jnp.take(jnp.asarray(rnd.addm, dtype=dtype), idx, axis=0)
            tm = jnp.take(jnp.asarray(rnd.tmask), idx, axis=0)
            lh = jnp.take(jnp.asarray(rnd.glh), idx, axis=0)
            payload = _gather_lanes(arena, Lh_f, g, lh,
                                    bool(rnd.glh.any()))
            moved = lax.ppermute(payload, "xy", rnd.perm)
            moved = jnp.where(tm[:, None, None],
                              jnp.swapaxes(moved, -1, -2), moved)
            cur = _gi(arena, s_)
            arena = arena.at[s_].set(
                moved + am[:, None, None] * cur,
                mode="promise_in_bounds")
    return arena


def _overlap_finish(ov, arena, Dinv_f, idx, r, c, b, dtype):
    """Trailing boundary compute + A⁻¹ extraction from the arena."""
    for op in ov.compute_at[len(ov.rounds)]:
        arena = _overlap_compute(ov, op, arena, Dinv_f, idx, r, c, b,
                                 dtype)
    with _scope("finish"):
        return lax.slice_in_dim(
            arena, 0, ov.n_ainv).reshape(ov.nbr, ov.nbc, b, b)


def make_sweep_overlapped(prog: PSelInvProgram, batched: bool = False):
    """Build the cross-level overlapped SPMD sweep from the compiled
    global round stream (`plan.schedule_overlapped`).

    One flat per-device **arena** of (b, b) blocks holds A⁻¹, the
    compact recycled Û slot pool, and the shared partial / S regions
    every level aliases (liveness windows + generation-keyed
    anti-dependences in the scheduler make the reuse safe — the executor
    just follows the tables). The read-only input L̂ shard is *not*
    copied into the arena: xfer-in lanes gather from it directly through
    the rounds' per-lane ``glh``/``lglh`` masks, shaving N blocks off
    the per-device footprint. The sweep is a single sequence of
    coalesced multi-lane ppermute rounds
    with per-lane gather/scatter/accumulate/transpose tables, and the
    masked level GEMMs (plus column/diagonal writes) fire at the round
    boundaries the dependence scheduler pinned them to — level L+1's
    xfer-in and col-bcast lanes ride the same rounds as level L's
    reduce / xfer-out / diag traffic instead of waiting for a level
    barrier. Call inside shard_map over a 1-D mesh axis "xy" of size
    pr*pc, with per-device blocks Lh, Dinv: (nbr, nbc, b, b);
    ``batched=True`` builds the multi-matrix variant (leading batch
    axis on the value tensors; see :func:`_wrap_sweep`)."""
    ov = prog.overlap_plan
    if ov is None:
        raise ValueError("build_program(...) first")
    b, pc = prog.b, prog.pc
    N = ov.n_ainv

    def body(Lh, Dinv):
        idx = lax.axis_index("xy")
        r = idx // pc
        c = idx % pc
        dtype = Lh.dtype
        Lh_f = Lh.reshape(N, b, b)
        Dinv_f = Dinv.reshape(N, b, b)
        # structless supernodes (leaves without fill + grid padding)
        arena = _overlap_init(ov, b, Dinv_f, idx, dtype)
        for t in range(len(ov.rounds)):
            arena = _overlap_round(ov, t, arena, Lh_f, Dinv_f, idx, r, c,
                                   b, dtype)
        return _overlap_finish(ov, arena, Dinv_f, idx, r, c, b, dtype)

    return _wrap_sweep(body, batched)


def make_sweep_segments(prog: PSelInvProgram,
                        boundaries: Optional[Sequence[int]] = None):
    """Profiling decomposition of the overlapped sweep: the same
    per-device body as :func:`make_sweep_overlapped`, cut at round
    boundaries so ``obs.rounds`` can jit, fence (``block_until_ready``)
    and time each executed round in isolation.

    Returns ``(init, steps, final)`` in the single-matrix shard_map
    calling convention (per-device value shards ``(1, nbr, nbc, b, b)``
    under ``in_specs=P("xy")``; the arena travels between segments as a
    per-device ``(1, arena_blocks, b, b)`` shard):

    * ``init(Lh, Dinv) -> arena`` — zeroed arena + structless-supernode
      diagonal seeds;
    * ``steps[i](arena, Lh, Dinv) -> arena`` — executed rounds
      ``boundaries[i] .. boundaries[i+1])`` (each = boundary compute ops
      + owner-local moves + the coalesced ppermute), one entry per
      consecutive boundary pair;
    * ``final(arena, Lh, Dinv) -> Ainv`` — the trailing boundary compute
      + A⁻¹ extraction.

    ``boundaries`` defaults to ``range(nrounds + 1)`` — one step per
    executed round; pass a coarser monotone cut list for level-chunk
    granularity. Running ``init``, every step in order, then ``final``
    reproduces the fused sweep bit-for-bit: the segments call the very
    same ``_overlap_round`` code, merely split at jit boundaries.
    Requires an overlapped schedule (stream programs carry one too —
    their gated tables are lowered from it)."""
    ov = prog.overlap_plan
    if ov is None:
        raise ValueError("build_program(...) first")
    b, pc = prog.b, prog.pc
    N = ov.n_ainv
    nrounds = len(ov.rounds)
    if boundaries is None:
        boundaries = list(range(nrounds + 1))
    else:
        boundaries = [int(x) for x in boundaries]
        if (not boundaries or boundaries[0] != 0
                or boundaries[-1] != nrounds
                or any(a >= b_ for a, b_ in zip(boundaries,
                                                boundaries[1:]))):
            raise ValueError(
                f"boundaries must be a strictly increasing cut list from "
                f"0 to {nrounds}, got {boundaries!r}")

    def _ctx(Lh, Dinv):
        idx = lax.axis_index("xy")
        return (idx, idx // pc, idx % pc, Lh[0].reshape(N, b, b),
                Dinv[0].reshape(N, b, b), Lh.dtype)

    def init(Lh, Dinv):
        idx, _, _, _, Dinv_f, dtype = _ctx(Lh, Dinv)
        return _overlap_init(ov, b, Dinv_f, idx, dtype)[None]

    def _make_step(lo: int, hi: int):
        def step(arena, Lh, Dinv):
            idx, r, c, Lh_f, Dinv_f, dtype = _ctx(Lh, Dinv)
            a = arena[0]
            for t in range(lo, hi):
                a = _overlap_round(ov, t, a, Lh_f, Dinv_f, idx, r, c, b,
                                   dtype)
            return a[None]
        return step

    steps = [_make_step(lo, hi)
             for lo, hi in zip(boundaries, boundaries[1:])]

    def final(arena, Lh, Dinv):
        idx, r, c, _, Dinv_f, dtype = _ctx(Lh, Dinv)
        return _overlap_finish(ov, arena[0], Dinv_f, idx, r, c, b,
                               dtype)[None]

    return init, steps, final


# ---------------------------------------------------------------------------
# stream path: the whole sweep as one lax.fori_loop over uniform tables
# ---------------------------------------------------------------------------

def make_sweep_stream(prog: PSelInvProgram, batched: bool = False):
    """Build the uniform round-stream SPMD sweep: the entire overlapped
    schedule as ONE ``lax.fori_loop`` body over the round-indexed device
    tables of ``core/stream.py`` (:class:`~.stream.StreamTables`).

    Each iteration ``t`` (a) dispatches the boundary's compute slots —
    level GEMM / column write / S-einsum / diagonal write behind
    per-round phase flags, one ``lax.switch`` per slot whose branches
    dynamic-index the level-stacked tables — (b) applies the owner-local
    copy lanes, and (c) runs the grid-factored comm-slot dictionary of
    ``core/stream.py``: one *static* ``ppermute`` per comm slot (a
    single grid-torus offset's pair union at one lane width), each gated
    by the round's ``slot_active`` mask through ``lax.cond`` so an
    inactive slot ships nothing, with per-round
    ``dynamic_slice``-selected gather/scatter/accumulate/transpose/
    L̂-gather lane tables (padded lanes scatter into the trash block,
    exactly like the overlapped executor's coalescing padding; a gated-off
    slot's zero arrival is never selected — no device receives on an
    inactive slot). The replayed round order, lane order and
    accumulation order are identical to :func:`make_sweep_overlapped`'s,
    so the f64 output is bit-identical — but jaxpr/HLO size no longer
    grows with the round count: the rounds are data (a few stacked
    tables), not code, and a round pays wire only for the slots it
    actually uses. Call under shard_map exactly like
    :func:`make_sweep_overlapped`; ``batched=True`` builds the
    multi-matrix variant."""
    st = prog.stream_tables
    if st is None:
        raise ValueError(
            "build_program(..., options=PlanOptions(stream=True)) first")
    b = prog.b
    pr, pc = st.pr, st.pc
    P = pr * pc
    nbr, nbc = st.nbr, st.nbc
    N = st.n_ainv
    NK = st.NK
    S = st.nslots
    slot_perms = [[(int(s), int(d)) for (s, d) in perm]
                  for perm in st.slot_perm]
    slot_w = [int(w) for w in st.slot_width]
    # static whole-table checks: streams/locals that never carry an
    # L̂-gathering lane skip the second gather entirely
    comm_any_lh = bool(st.glh.any()) if S else False
    local_any_lh = bool(st.lglh.any()) if st.LW else False

    def body(Lh, Dinv):
        idx = lax.axis_index("xy")
        r = idx // pc
        c = idx % pc
        dtype = Lh.dtype
        Lh_f = Lh.reshape(N, b, b)
        Dinv_f = Dinv.reshape(N, b, b)
        with _scope("init"):
            arena = jnp.zeros((st.arena_blocks, b, b), dtype=dtype)
            # structless supernodes (leaves without fill + grid padding)
            if len(st.diag_set_root):
                slots = jnp.asarray(st.diag_set_slot)
                m = (jnp.asarray(st.diag_set_root) == idx).astype(dtype)
                arena = arena.at[slots].add(
                    m[:, None, None] * _gi(Dinv_f, slots),
                    mode="promise_in_bounds")

        # round-stacked device tables: one closed-over constant each,
        # sliced per round inside the loop body
        G = jnp.asarray(st.gather)
        SCT = jnp.asarray(st.scatter)
        AM = jnp.asarray(st.addm, dtype=dtype)
        TM = jnp.asarray(st.tmask)
        GLH = jnp.asarray(st.glh)
        RSL = jnp.asarray(st.recv_slot)
        ACT = jnp.asarray(st.slot_active)
        LG = jnp.asarray(st.lgather)
        LS = jnp.asarray(st.lscatter)
        LT = jnp.asarray(st.ltmask)
        LLH = jnp.asarray(st.lglh)
        CK = jnp.asarray(st.comp_kind)
        CL = jnp.asarray(st.comp_level)
        # level-stacked compute tables (padded to the widest level)
        UG = jnp.asarray(st.u_gather)
        CM = jnp.asarray(st.cmask, dtype=dtype)
        KCS = jnp.asarray(st.kcs)
        KRS = jnp.asarray(st.krs)
        CWR = jnp.asarray(st.col_write_row, dtype=dtype)
        CWC = jnp.asarray(st.col_write_col, dtype=dtype)
        DRM = jnp.asarray(st.diag_rowmask, dtype=dtype)
        DRT = jnp.asarray(st.diag_root)
        DSL = jnp.asarray(st.diag_slot)

        def at(tab, i):
            return lax.dynamic_index_in_dim(tab, i, 0, keepdims=False)

        # ---- the four compute phases, level selected dynamically ------
        # numerics live in the shared _phase_* helpers (one definition
        # with the unrolled overlapped executor); these branches only
        # dynamic-index the level-stacked tables, padded to NK: padded
        # rows carry zero struct masks (exact zeros into the shared
        # regions' tails) and trash diag slots — numerically inert
        def br_noop(L, arena):
            return arena

        def br_gemm(L, arena):
            ut = jnp.take(at(UG, L), idx, axis=0)        # (NK*nbc,)
            cm = jnp.take(at(CM, L), c, axis=0)          # (NK, nbc)
            return _phase_gemm(arena, ut, cm, N, nbr, nbc, b, st.base_p)

        def br_write(L, arena):
            wr = jnp.take(at(CWR, L), r, axis=0)         # (NK, nbr)
            wc = jnp.take(at(CWC, L), c, axis=0)         # (NK,)
            return _phase_write(arena, at(KCS, L), wr, wc,
                                N, nbr, nbc, b, st.base_p)

        def br_scomp(L, arena):
            ut = jnp.take(at(UG, L), idx, axis=0)
            cm = jnp.take(at(CM, L), c, axis=0)
            rm = jnp.take(at(DRM, L), r, axis=0)         # (NK,)
            return _phase_scomp(arena, ut, cm, at(KRS, L), rm,
                                N, nbr, nbc, b, st.base_s)

        def br_diagw(L, arena):
            return _phase_diagw(arena, Dinv_f, at(DSL, L), at(DRT, L),
                                idx, N, st.base_s, dtype)

        # branch order is the COMP_* id order — wired explicitly so the
        # phase-flag encoding can't drift from the dispatch table
        branches = [None] * 5
        branches[COMP_NOOP] = br_noop
        branches[COMP_GEMM] = br_gemm
        branches[COMP_WRITE] = br_write
        branches[COMP_SCOMP] = br_scomp
        branches[COMP_DIAGW] = br_diagw

        def round_body(t, arena):
            # (a) this boundary's compute slots, in dependence order
            if st.C:
                ck = at(CK, t)
                cl = at(CL, t)
                for j in range(st.C):
                    arena = lax.switch(ck[j], branches, cl[j], arena)
            # (b) owner-local copy lanes
            if st.LW:
                with _scope("lanes"):
                    lg = jnp.take(at(LG, t), idx, axis=0)
                    ls = jnp.take(at(LS, t), idx, axis=0)
                    ltm = jnp.take(at(LT, t), idx, axis=0)
                    llh = jnp.take(at(LLH, t), idx, axis=0)
                    blks = _gather_lanes(arena, Lh_f, lg, llh,
                                         local_any_lh)
                    blks = jnp.where(ltm[:, None, None],
                                     jnp.swapaxes(blks, -1, -2), blks)
                    arena = arena.at[ls].set(blks,
                                             mode="promise_in_bounds")
            # (c) comm: the device's one outgoing lane stack is gathered
            # once; each comm slot — gated by the round's active mask —
            # ships the stack's leading slot_width lanes along its
            # static union perm, and each receiver keeps only the
            # arrival of its one receive slot and scatters it once —
            # identical snapshot semantics to the unrolled round's
            # single gather/permute/scatter. An inactive slot's cond
            # ships nothing (zeros branch); no device receives on an
            # inactive slot, so the zeros are never selected.
            if S:
                with _scope("permute"):
                    g = jnp.take(at(G, t), idx, axis=0)      # (W,)
                    lh = jnp.take(at(GLH, t), idx, axis=0)
                    payload = _gather_lanes(arena, Lh_f, g, lh,
                                            comm_any_lh)
                    rsl = jnp.take(at(RSL, t), idx, axis=0)  # scalar
                    act = at(ACT, t)                         # (S,) bool
                    moved = jnp.zeros_like(payload)
                    for si in range(S):
                        w = slot_w[si]
                        mv = lax.cond(
                            act[si],
                            lambda p, perm=slot_perms[si]:
                                lax.ppermute(p, "xy", perm),
                            lambda p: jnp.zeros_like(p),
                            lax.slice_in_dim(payload, 0, w))
                        moved = moved.at[:w].set(
                            jnp.where(rsl == si, mv, moved[:w]))
                    tm = jnp.take(at(TM, t), idx, axis=0)
                    moved = jnp.where(tm[:, None, None],
                                      jnp.swapaxes(moved, -1, -2), moved)
                    s_ = jnp.take(at(SCT, t), idx, axis=0)
                    am = jnp.take(at(AM, t), idx, axis=0)
                    cur = _gi(arena, s_)
                    arena = arena.at[s_].set(
                        moved + am[:, None, None] * cur,
                        mode="promise_in_bounds")
            return arena

        # steps = nrounds + 1: the final iteration's comm tables are
        # all-trash no-ops and only the last boundary's compute fires
        arena = lax.fori_loop(0, st.steps, round_body, arena)
        with _scope("finish"):
            return lax.slice_in_dim(arena, 0, N).reshape(nbr, nbc, b, b)

    return _wrap_sweep(body, batched)


# ---------------------------------------------------------------------------
# host-side data preparation / gather
# ---------------------------------------------------------------------------

def validate_uniform_widths(bs: BlockStructure, b: int) -> None:
    """The dense-blocked layout requires every supernode at width b —
    one check shared by every structure entry point (matrix or ready
    :class:`BlockStructure`)."""
    if not np.all(bs.widths() == b):
        raise ValueError(
            f"structure has non-uniform supernode widths "
            f"{sorted(set(bs.widths().tolist()))} — the dense-blocked "
            f"layout requires every supernode to have width exactly "
            f"b={b}")


def pad_nb(nsuper: int, pr: int, pc: int) -> int:
    """Pad the supernode count so both grid dims divide it (the one
    padding rule — engine cache keys depend on it being identical for
    every entry point)."""
    nb = nsuper
    while nb % pr or nb % pc:
        nb += 1
    return nb


def analyze_structure(A, b: int, pr: int, pc: int
                      ) -> Tuple[BlockStructure, int]:
    """The value-independent half of the host prep: symbolic
    factorization + uniform-width validation + grid padding. Everything
    the engine caches hangs off this (bs, nb) pair."""
    import scipy.sparse as sp

    A = sp.csr_matrix(A)
    n = A.shape[0]
    # real input validation, not asserts: these guard user-provided
    # matrices and must survive ``python -O``
    if n % b:
        raise ValueError(
            f"matrix size n={n} is not a multiple of the supernode block "
            f"size b={b} — pad the matrix (or pick b dividing n)")
    bs = symbolic_factorize(A, max_supernode=b)
    validate_uniform_widths(bs, b)
    return bs, pad_nb(bs.nsuper, pr, pc)


def check_values_pattern(A, bs: BlockStructure, b: int):
    """Validate one matrix's *pattern* against an analyzed structure.

    The structured factorization only ever visits blocks in
    ``bs.struct``, so a matrix whose pattern escapes the analyzed
    structure would be silently truncated into the selected inverse of a
    *different* matrix — reject it instead (O(nnz) block-coordinate
    check against the symmetric filled pattern). Returns the matrix as
    CSR. Shared by :func:`prepare_values_many` and the serving layer's
    per-request admission check (``repro.serve``) — a bad request must
    be rejectable *before* it joins a batch, so its neighbors still
    solve."""
    import scipy.sparse as sp

    A = sp.csr_matrix(A)
    n = A.shape[0]
    if n != int(bs.offsets[-1]):
        raise ValueError(
            f"matrix size n={n} does not match the analyzed structure "
            f"(expected n={int(bs.offsets[-1])}) — re-run analyze for a "
            "different-sized matrix")
    nb0 = bs.nsuper
    present = np.zeros((nb0, nb0), dtype=bool)
    np.fill_diagonal(present, True)
    for K in range(nb0):
        present[np.asarray(bs.struct[K], dtype=np.int64), K] = True
    coo = A.tocoo()
    hi = np.maximum(coo.row // b, coo.col // b)
    lo = np.minimum(coo.row // b, coo.col // b)
    bad = (coo.data != 0) & ~present[hi, lo]
    if bad.any():
        blocks = sorted({(int(i), int(j))
                         for i, j in zip(hi[bad], lo[bad])})[:8]
        raise ValueError(
            f"matrix has {int(bad.sum())} nonzero(s) outside the "
            f"analyzed block structure (e.g. blocks {blocks}) — its "
            "sparsity pattern differs from the analyzed matrix; re-run "
            "analyze for this structure")
    return A


def _shard_blocks(G: np.ndarray, nb: int, b: int, pr: int,
                  pc: int) -> np.ndarray:
    """Dense (…, nb, nb, b, b) block grid → (…, pr*pc, nbr, nbc, b, b)
    device shards for ``in_specs=P("xy")`` (cyclic over both grid dims).
    The one layout rule — :func:`prepare_values_many` and
    :func:`gather_blocks` must agree."""
    nbr, nbc = nb // pr, nb // pc
    lead = G.shape[:-4]
    G = G.reshape(lead + (nbr, pr, nbc, pc, b, b))
    perm = tuple(range(len(lead)))
    off = len(lead)
    G = G.transpose(perm + (off + 1, off + 3, off, off + 2,
                            off + 4, off + 5))
    return G.reshape(lead + (pr * pc, nbr, nbc, b, b))


def prepare_values(A, bs: BlockStructure, nb: int, b: int, pr: int,
                   pc: int) -> Tuple[np.ndarray, np.ndarray]:
    """Numeric host prep of one matrix against an already-analyzed
    structure: :func:`prepare_values_many` at B=1, its stacked shards'
    only entry.

    Returns (Lh, Dinv) with shape (pr*pc, nbr, nbc, b, b) for
    ``in_specs=P("xy")``. The caller guarantees ``A`` has the sparsity
    structure that produced ``bs`` — this is the engine's analyze-once /
    solve-many hot path, so no symbolic work happens here. A pattern
    that escapes the structure raises :func:`check_values_pattern`'s own
    ``ValueError``. Traced as :func:`prepare_values_many` is, with
    ``B=1``."""
    Lh, Dinv = prepare_values_many([A], bs, nb, b, pr, pc)
    return Lh[0], Dinv[0]


def _scatter_blocks(csr: Sequence, nb0: int, b: int, span) -> np.ndarray:
    """B same-size CSR matrices → the zeroed f64 ``(B, nb0, nb0, b, b)``
    block workspace holding each stored entry ``(r, c)`` at
    ``[i, r // b, c // b, r % b, c % b]``: exactly what densifying each
    matrix and blocking it gives, without the n×n copies. A matrix with
    no duplicate entries (its indices sorted on a copy when they are
    not) is one indexed assignment; one with duplicates sums them first,
    in the data's own dtype and storage order, as ``todense`` does. Sets
    ``nnz`` and ``summed`` (the matrices that took the summing path) on
    ``span``; the matrices are only read."""
    W = np.zeros((len(csr), nb0, nb0, b, b))
    nnz = summed = 0
    for i, M in enumerate(csr):
        S = M if M.has_sorted_indices else M.sorted_indices()
        dup = not S.has_canonical_format
        if not dup:
            M = S
        r = np.repeat(np.arange(M.shape[0]), np.diff(M.indptr))
        c = M.indices
        flat = ((r // b * nb0 + c // b) * b + r % b) * b + c % b
        Wi = W[i].reshape(-1)                       # a view of W[i]
        if dup:
            flat, at = np.unique(flat, return_inverse=True)
            vals = np.zeros(flat.size, dtype=M.dtype)
            np.add.at(vals, at, M.data)
            Wi[flat] = vals
            summed += 1
        else:
            Wi[flat] = M.data + 0     # + 0 folds -0.0 to 0.0, as todense
        nnz += M.nnz
    span.set(nnz=nnz, summed=summed)
    return W


def prepare_values_many(mats: Sequence, bs: BlockStructure, nb: int,
                        b: int, pr: int, pc: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched host factorization: B same-structure matrices → stacked
    ``(B, pr*pc, nbr, nbc, b, b)`` shards in ONE structure-driven pass.

    Right-looking supernodal elimination over the filled structure, in
    supernode order, with no pivoting across supernodes — all in f64,
    with every block stacked ``(B, b, b)``. Each supernode K costs a few
    BLAS-3 calls per batch member, on the Schur-updated blocks ``A``:

    - ``D⁻¹(K,K) = A(K,K)⁻¹`` (one batched LAPACK inverse; it equals
      ``U_KK⁻¹·L_KK⁻¹`` of the no-pivot LU, which is never formed);
    - ``L̂(C,K) = L(C,K)·L_KK⁻¹ = A(C,K)·A(K,K)⁻¹``, one
      ``(|C|·b, b) @ (b, b)`` GEMM over ``C = struct(K)``;
    - the Schur update of the whole ``C × C`` clique,
      ``A(C,C) -= L(C,K)·U(K,C) = L̂(C,K)·A(K,C)``, one
      ``(|C|·b, b) @ (b, |C|·b)`` GEMM.

    The dense (nb0, nb0) block workspace is the same asymptotic
    footprint as the device layout emitted here, and is freed before
    the layout copies. Numerics match the supernodal LU + triangular
    solves of ``supernodal_lu.factorize`` to rounding (≤1e-12, asserted
    in tests).

    Raises ``ValueError`` naming the offending batch *index* (for B > 1)
    when any matrix's pattern escapes the analyzed structure — callers
    that need per-request isolation (the serving layer) validate each
    matrix with :func:`check_values_pattern` first.

    Traced as ``prep.check``, ``prep.densify`` (the CSR entries
    scattered into the zeroed block workspace; with ``nnz``, the entries
    scattered, and ``summed``, how many matrices took the
    duplicate-summing path), ``prep.factor`` (the supernode loop, L̂ and
    D⁻¹) and ``prep.layout``, each with ``B``."""
    if not len(mats):
        raise ValueError("prepare_values_many needs at least one matrix")
    B, nb0 = len(mats), bs.nsuper
    csr = []
    with TRACER.span("prep.check", B=B):
        for i, M in enumerate(mats):
            try:
                csr.append(check_values_pattern(M, bs, b))
            except ValueError as e:
                if B == 1:
                    raise
                raise ValueError(f"matrix {i} of {B}: {e}") from e

    # dense (B, nb0, nb0, b, b) block workspace holding the evolving
    # Schur complement, zeroed and then written with each matrix's
    # stored entries (no n×n dense copy); fill lands in blocks the
    # symbolic structure already owns, so reading only struct blocks
    # below is exact
    with TRACER.span("prep.densify", B=B) as span:
        W = _scatter_blocks(csr, nb0, b, span)
    with TRACER.span("prep.factor", B=B):
        Lh = np.zeros((B, nb, nb, b, b))
        Dinv = np.zeros((B, nb, nb, b, b))
        bidx = np.arange(B)
        for K in range(nb0):
            DKK = np.linalg.inv(W[:, K, K])        # = U_KK⁻¹·L_KK⁻¹
            Dinv[:, K, K] = DKK
            C = [int(i) for i in bs.struct[K]]
            if not C:
                continue
            c = len(C)
            LhCK = W[:, C, K].reshape(B, c * b, b) @ DKK
            Lh[:, C, K] = LhCK.reshape(B, c, b, b)
            # A(C,C) -= L̂(C,K)·A(K,C) over the whole clique, one GEMM
            AKC = W[:, K, C].transpose(0, 2, 1, 3).reshape(B, b, c * b)
            W[np.ix_(bidx, C, C)] -= (LhCK @ AKC).reshape(
                B, c, b, c, b).transpose(0, 1, 3, 2, 4)
        del W                         # room for the layout's copies
    with TRACER.span("prep.layout", B=B):
        Dinv[:, range(nb0, nb), range(nb0, nb)] = np.eye(b)  # padding
        return (_shard_blocks(Lh, nb, b, pr, pc),
                _shard_blocks(Dinv, nb, b, pr, pc))


def check_grid_devices(pr: int, pc: int) -> None:
    """Raise the canonical diagnostic when the process grid oversubscribes
    the available JAX devices."""
    avail = len(jax.devices())
    if pr * pc > avail:
        raise ValueError(
            f"process grid {pr}x{pc} needs {pr * pc} devices but only "
            f"{avail} JAX device(s) are available — shrink the grid or "
            "launch with more devices (e.g. XLA_FLAGS="
            f"--xla_force_host_platform_device_count={pr * pc})")


def gather_blocks(out: np.ndarray, prog) -> np.ndarray:
    """Invert the shard layout back to a dense (nb, nb, b, b) block grid.
    Accepts the :class:`PSelInvProgram` or anything carrying one under
    ``.program`` (the engine) — the geometry is derived, not re-passed."""
    prog = getattr(prog, "program", prog)
    nb, b, pr, pc = prog.nb, prog.b, prog.pr, prog.pc
    nbr, nbc = nb // pr, nb // pc
    return (out.reshape(pr, pc, nbr, nbc, b, b)
               .transpose(2, 0, 3, 1, 4, 5)
               .reshape(nb, nb, b, b))
