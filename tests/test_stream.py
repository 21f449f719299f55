"""Stream lowering tests: the uniform round-stream executor
(``core/stream.py`` + ``pselinv_dist.make_sweep_stream``).

(a) replay property — the round-indexed (R, P, W) tables reproduce the
    unrolled :class:`~.plan.GlobalRound` list round-for-round: same
    (src, dst, gather slot, scatter slot, add, transpose, L̂-gather)
    lanes, same owner-local moves, same compute boundaries, same
    byte-accounted edges — padded lanes all masked into the trash block;
(b) accounting — ``round_schedule_from_stream`` equals
    ``round_schedule_from_overlap`` event-for-event (simulated bytes
    still equal executed bytes) and ``round_schedule_of`` routes stream
    programs through it;
(c) execution — ``make_sweep_stream`` (one ``lax.fori_loop`` body) is
    f64 bit-identical to the unrolled overlapped executor and matches the
    serial selected inverse to 1e-12 at nb=16 (tier-1) and nb=32
    (``slow`` marker, excluded from tier-1 by default);
(d) wiring — ``PlanOptions(stream=True)`` flows through engine
    analyze/solve/stats (compile metrics included).
"""
import numpy as np
import pytest
import scipy.sparse as sp

from conftest import run_sub

from repro.core import sparse
from repro.core.plan import (PlanOptions, build_plan, schedule_overlapped,
                             schedule_stream)
from repro.core.schedule import Grid2D
from repro.core.simulator import (round_schedule_from_overlap,
                                  round_schedule_from_stream,
                                  round_schedule_of, simulate_schedule)
from repro.core.stream import (COMP_KIND_ID, decode_local_lanes,
                               decode_round_lanes, lower_stream)
from repro.core.symbolic import symbolic_factorize
from repro.core.trees import TreeKind


@pytest.fixture(scope="module", params=[None, 1])
def ov_st(request):
    """nb=32 plan on grid 4×2 → (plan, overlapped lowering, stream
    tables), with and without a Û liveness window (window=1 forces slot
    recycling through the stream tables too)."""
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(32, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=32)
    ov = schedule_overlapped(plan, window=request.param)
    return plan, ov, lower_stream(ov)


def _round_real_lanes(ov, rnd):
    """The overlapped round's real comm lanes in the decode tuple form
    (a real lane is one whose receiver scatter slot is not trash)."""
    out = set()
    for (s, d) in rnd.perm:
        for j in range(rnd.width):
            ds = int(rnd.scatter[d, j])
            if ds == ov.trash:
                continue
            out.add((s, d, int(rnd.gather[s, j]), ds,
                     float(rnd.addm[d, j]), bool(rnd.tmask[d, j]),
                     bool(rnd.glh[s, j])))
    return out


def test_stream_tables_replay_rounds(ov_st):
    """The replay property: every comm lane, owner-local move and
    compute boundary of the unrolled GlobalRound list is reproduced
    round-for-round by the uniform tables, and nothing else — the padded
    lanes all land in the trash block."""
    plan, ov, st = ov_st
    P = ov.pr * ov.pc
    assert st.nrounds == len(ov.rounds)
    assert st.steps == st.nrounds + 1
    assert st.arena_blocks == ov.arena_blocks and st.trash == ov.trash

    n_real = 0
    for t, rnd in enumerate(ov.rounds):
        decoded = set(decode_round_lanes(st, t))
        expect = _round_real_lanes(ov, rnd)
        assert decoded == expect, f"round {t} comm lanes drifted"
        n_real += len(expect)
        # byte-movement metadata is the round's, verbatim
        assert st.lane_edges[t] == rnd.edges
        # local moves: real lanes match, the LW padding is all-trash
        dec_loc = set(decode_local_lanes(st, t))
        exp_loc = set()
        for dev in range(P):
            for j in range(rnd.lwidth):
                ds = int(rnd.lscatter[dev, j])
                if ds == ov.trash:
                    continue
                exp_loc.add((dev, int(rnd.lgather[dev, j]), ds,
                             bool(rnd.ltmask[dev, j]),
                             bool(rnd.lglh[dev, j])))
        assert dec_loc == exp_loc, f"round {t} local lanes drifted"
    assert n_real == sum(len(r.edges) for r in ov.rounds)

    # the final fori_loop iteration is a comm no-op: all-trash tables
    assert not decode_round_lanes(st, st.nrounds)
    assert not decode_local_lanes(st, st.nrounds)

    # compute boundaries: same ops, same dependence order, same levels
    for t, ops in enumerate(ov.compute_at):
        got = [(int(k), int(l))
               for k, l in zip(st.comp_kind[t], st.comp_level[t]) if k]
        assert got == [(COMP_KIND_ID[op.kind], op.level) for op in ops]

    # level tables: the real prefix is the overlapped level's, the NK
    # padding is inert (trash Û lanes, zero masks, no-device diag root)
    for L, lv in enumerate(ov.levels):
        nk = len(lv.Ks)
        nbc = ov.nbc
        np.testing.assert_array_equal(st.u_gather[L, :, :nk * nbc],
                                      lv.u_gather)
        assert (st.u_gather[L, :, nk * nbc:] == st.trash).all()
        np.testing.assert_array_equal(st.cmask[L, :, :nk], lv.cmask)
        assert (st.cmask[L, :, nk:] == 0).all()
        assert (st.diag_root[L, nk:] == -1).all()
        assert (st.diag_slot[L, nk:] == st.trash).all()


def test_stream_round_schedule_matches_overlap(ov_st):
    """Simulated bytes equal executed bytes, stream edition: the
    timeline derived from the stream tables equals the overlapped
    executor's event-for-event, and the α-β simulator times both to the
    same total."""
    plan, ov, st = ov_st
    rs_o = round_schedule_from_overlap(ov, plan)
    rs_s = round_schedule_from_stream(st, plan)
    assert rs_s.nranks == rs_o.nranks
    assert rs_s.peak_arena_blocks == rs_o.peak_arena_blocks
    assert len(rs_s.events) == len(rs_o.events)
    for (wa, pa), (wb, pb) in zip(rs_o.events, rs_s.events):
        assert wa == wb
        if wa == "comp":
            np.testing.assert_array_equal(pa, pb)
        else:
            assert pa == pb
    sim_o = simulate_schedule(rs_o)
    sim_s = simulate_schedule(rs_s)
    assert sim_s.total_time == sim_o.total_time


def test_round_schedule_of_routes_stream_programs():
    """A stream-compiled program's executed timeline comes from its own
    tables (``round_schedule_from_stream``), not the overlapped object
    it was lowered from — and matches it."""
    from repro.core.pselinv_dist import build_program
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(12, 8)), max_supernode=8)
    prog = build_program(bs, 12, 8, 4, 2,
                         options=PlanOptions(stream=True))
    assert prog.stream_tables is not None
    rs = round_schedule_of(prog)
    rs_o = round_schedule_from_overlap(prog.overlap_plan, prog.plan)
    assert len(rs.events) == len(rs_o.events)
    assert simulate_schedule(rs).total_time == \
        simulate_schedule(rs_o).total_time


def test_schedule_stream_single_device():
    """Degenerate grid (1×1): no comm at all — the stream has an empty
    shift set and the tables still replay the (local + compute only)
    rounds."""
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(8, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(1, 1), TreeKind.SHIFTED, nb=8)
    ov, st = schedule_stream(plan)
    assert st.shifts == () and st.nslots == 0 and st.W == 0
    assert st.nrounds == len(ov.rounds)
    assert (st.recv_slot == -1).all()
    assert st.slot_active.shape == (st.steps, 0)
    for t in range(st.steps):
        assert not decode_round_lanes(st, t)


def test_stream_shift_mask_replay(ov_st):
    """Gated-slot property test: the per-round shift-mask tables decode
    back to exactly the GlobalRound lane sets, round for round. Every
    slot perm is a single grid-offset bijection; a round's recv-slot
    assignments derive exactly the slots its gate row activates; the
    union of active slots covers exactly the round's permute pairs; and
    the executed-wire number from the gate table equals the simulator's
    independent recv-slot lens (simulated == executed, wire edition)."""
    import types

    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import stream_shifts_per_round, \
        stream_wire_blocks, stream_wire_bytes

    plan, ov, st = ov_st
    pr, pc = st.pr, st.pc
    assert st.axis_factored and st.nslots > 0

    for si, perm in enumerate(st.slot_perm):
        offs = {((d // pc - s // pc) % pr, (d % pc - s % pc) % pc)
                for (s, d) in perm}
        assert offs == {tuple(st.slot_shift[si])}, \
            f"slot {si} mixes grid offsets {offs}"
        assert len({s for s, _ in perm}) == len(perm)
        assert len({d for _, d in perm}) == len(perm)
        assert 1 <= st.slot_width[si] <= st.W

    for t, rnd in enumerate(ov.rounds):
        gated = {si for si in range(st.nslots) if st.slot_active[t, si]}
        derived = {int(si) for si in st.recv_slot[t] if si >= 0}
        assert gated == derived, f"round {t} gate/receive drift"
        # the active slots cover exactly this round's permute pairs
        pairs = {(s, d) for (s, d) in rnd.perm}
        for (s, d) in pairs:
            si = int(st.recv_slot[t, d])
            assert (s, d) in st.slot_perm[si]
        # decoded gated lanes == GlobalRound lanes (the replay property,
        # through the gate-checking decode path)
        assert set(decode_round_lanes(st, t)) == _round_real_lanes(ov,
                                                                   rnd)
    assert not st.slot_active[st.nrounds].any()

    # wire accounting: gate-table blocks == the manual per-round sum,
    # and the simulator's independent lens prices the same bytes
    manual = sum(len(st.slot_perm[si]) * st.slot_width[si]
                 for t in range(st.steps)
                 for si in range(st.nslots) if st.slot_active[t, si])
    assert stream_wire_blocks(st) == manual
    prog = types.SimpleNamespace(b=8, stream_tables=st,
                                 overlap_plan=ov)
    assert executed_wire_bytes(prog) == stream_wire_bytes(st, 8)
    # gating executes fewer permutes per round than the flat-ring
    # encoding's every-shift-every-round
    assert 0 < stream_shifts_per_round(st) < len(st.shifts)


def test_stream_flat_ring_mode():
    """``axis_factored=False`` recovers the PR-5 flat-ring encoding —
    one always-active full-ring slot per used shift — through the same
    slot machinery, and the gated grid-factored lowering of the same
    plan ships strictly (>2×) fewer wire blocks."""
    from repro.core.stream import stream_wire_blocks

    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(16, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=16)
    ov_f, st_f = schedule_stream(plan, axis_factored=False)
    assert not st_f.axis_factored
    P = 8
    assert st_f.nslots == len(st_f.shifts)
    for si, perm in enumerate(st_f.slot_perm):
        dlt = st_f.slot_shift[si]
        assert dlt == ((perm[0][1] - perm[0][0]) % P,)
        assert len(perm) == P and st_f.slot_width[si] == st_f.W
    assert st_f.slot_active.all()
    assert stream_wire_blocks(st_f) == \
        st_f.steps * st_f.nslots * P * st_f.W
    # flat mode still replays the identical lanes
    for t, rnd in enumerate(ov_f.rounds):
        assert set(decode_round_lanes(st_f, t)) == _round_real_lanes(
            ov_f, rnd)

    ov_g, st_g = schedule_stream(plan)
    assert 2 * stream_wire_blocks(st_g) < stream_wire_blocks(st_f)


def test_stream_shift_budget_coarsens():
    """``shift_budget`` trades wire for fewer gated permutes: the slot
    dictionary shrinks to the budget (or one slot per grid offset), the
    replay property still holds lane-for-lane, and the wire cost sits
    between the exact-width dictionary's and the flat ring's."""
    from repro.core.stream import stream_wire_blocks

    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(16, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=16)
    ov, st = schedule_stream(plan)
    noffs = len({tuple(sh) for sh in st.slot_shift})
    ovb, stb = schedule_stream(plan, shift_budget=noffs)
    assert stb.nslots <= noffs < st.nslots
    for t, rnd in enumerate(ovb.rounds):
        assert set(decode_round_lanes(stb, t)) == _round_real_lanes(
            ovb, rnd)
    ov_f, st_f = schedule_stream(plan, axis_factored=False)
    assert stream_wire_blocks(st) <= stream_wire_blocks(stb) \
        < stream_wire_blocks(st_f)
    with pytest.raises(ValueError, match="one comm slot per grid "
                                         "offset"):
        schedule_stream(plan, shift_budget=1)
    with pytest.raises(ValueError, match="axis_factored=True"):
        PlanOptions(stream=True, axis_factored=False, shift_budget=4)


def test_stream_tables_grid8x4():
    """Tentpole validation at grid 8×4, where the flat ring pays ~200×
    unrolled wire: host-side lowering replays lane-for-lane, simulated
    wire equals executed wire from the gated tables, and the gated
    encoding lands within 4× of the unrolled executor's wire (the flat
    ring's every-shift-every-round is >25× here)."""
    import types

    from repro.core.simulator import executed_wire_bytes
    from repro.core.stream import overlap_wire_blocks, \
        stream_shifts_per_round, stream_wire_blocks, stream_wire_bytes

    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(32, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(8, 4), TreeKind.SHIFTED, nb=32)
    ov, st = schedule_stream(plan)
    for t, rnd in enumerate(ov.rounds):
        assert set(decode_round_lanes(st, t)) == _round_real_lanes(ov,
                                                                   rnd)
    prog = types.SimpleNamespace(b=8, stream_tables=st, overlap_plan=ov)
    assert executed_wire_bytes(prog) == stream_wire_bytes(st, 8)

    wire_unrolled = overlap_wire_blocks(ov)
    wire_gated = stream_wire_blocks(st)
    _, st_f = schedule_stream(plan, axis_factored=False)
    wire_flat = stream_wire_blocks(st_f)
    assert wire_gated <= 4 * wire_unrolled, (wire_gated, wire_unrolled)
    assert wire_flat > 25 * wire_unrolled, (wire_flat, wire_unrolled)
    assert stream_shifts_per_round(st) < len(st.shifts) / 2


def test_stream_executor_bit_identical_nb16():
    """End-to-end f64: the fori_loop stream executor matches the
    unrolled overlapped executor exactly (≤1e-12 asserted, 0.0
    observed), the serial selected inverse to ≤1e-12 and the dense
    oracle on the selected pattern, at nb=16 on grid 4×2."""
    run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.core import sparse
        from repro.core.plan import PlanOptions
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import (analyze_structure,
                                             build_program, gather_blocks,
                                             make_sweep_overlapped,
                                             make_sweep_stream,
                                             prepare_values)
        from repro.core.selinv import dense_selinv_oracle, selected_inverse
        A = sparse.laplacian_2d(16, 8)
        b, pr, pc = 8, 4, 2
        bs, nb = analyze_structure(A, b, pr, pc)
        Lh_s, Dinv_s = prepare_values(A, bs, nb, b, pr, pc)
        devs = np.array(jax.devices()[:pr * pc]).reshape(pr * pc)
        mesh = Mesh(devs, ("xy",))
        Lh = jnp.asarray(Lh_s, jnp.float64)
        Dinv = jnp.asarray(Dinv_s, jnp.float64)

        def run(prog, mk):
            fn = jax.jit(shard_map(mk(prog), mesh=mesh,
                                   in_specs=(P("xy"), P("xy")),
                                   out_specs=P("xy")))
            return np.asarray(fn(Lh, Dinv))

        prog_t = build_program(bs, nb, b, pr, pc,
                               options=PlanOptions(stream=True))
        out_t = run(prog_t, make_sweep_stream)
        prog_o = build_program(bs, nb, b, pr, pc, TreeKind.SHIFTED)
        out_o = run(prog_o, make_sweep_overlapped)
        assert abs(out_t - out_o).max() <= 1e-12, abs(out_t - out_o).max()
        blocks = gather_blocks(out_t, prog_t)
        serial, _ = selected_inverse(A, max_supernode=b)
        d = max(abs(blocks[I, J] - np.asarray(blk)).max()
                for (I, J), blk in serial.items())
        assert d <= 1e-12, d

        ref = dense_selinv_oracle(A)
        err = 0.0
        for K in range(bs.nsuper):
            err = max(err, abs(blocks[K, K]
                               - ref[K*8:(K+1)*8, K*8:(K+1)*8]).max())
            for I in bs.struct[K]:
                I = int(I)
                err = max(err, abs(blocks[I, K]
                                   - ref[I*8:(I+1)*8, K*8:(K+1)*8]).max())
        assert err < 1e-9, err
        print("OK")
    """, x64=True)


@pytest.mark.slow
def test_stream_executor_bit_identical_nb32():
    """The nb=32 acceptance case (slow — excluded from tier-1 by the
    default ``-m "not slow"``; run with ``-m slow``): stream vs unrolled
    overlapped vs the serial selected inverse, f64, including a recycled
    arena (window=1) stream."""
    run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.core import sparse
        from repro.core.plan import PlanOptions
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import (analyze_structure,
                                             build_program, gather_blocks,
                                             make_sweep_overlapped,
                                             make_sweep_stream,
                                             prepare_values)
        from repro.core.selinv import dense_selinv_oracle, selected_inverse
        A = sparse.laplacian_2d(32, 8)
        b, pr, pc = 8, 4, 2
        bs, nb = analyze_structure(A, b, pr, pc)
        Lh_s, Dinv_s = prepare_values(A, bs, nb, b, pr, pc)
        devs = np.array(jax.devices()[:pr * pc]).reshape(pr * pc)
        mesh = Mesh(devs, ("xy",))
        Lh = jnp.asarray(Lh_s, jnp.float64)
        Dinv = jnp.asarray(Dinv_s, jnp.float64)

        def run(prog, mk):
            fn = jax.jit(shard_map(mk(prog), mesh=mesh,
                                   in_specs=(P("xy"), P("xy")),
                                   out_specs=P("xy")))
            return np.asarray(fn(Lh, Dinv))

        out_t = run(build_program(bs, nb, b, pr, pc,
                                  options=PlanOptions(stream=True)),
                    make_sweep_stream)
        out_w = run(build_program(bs, nb, b, pr, pc,
                                  options=PlanOptions(stream=True,
                                                      window=1)),
                    make_sweep_stream)
        prog_o = build_program(bs, nb, b, pr, pc, TreeKind.SHIFTED)
        out_o = run(prog_o, make_sweep_overlapped)
        assert abs(out_t - out_o).max() <= 1e-12, abs(out_t - out_o).max()
        assert abs(out_w - out_o).max() <= 1e-12, abs(out_w - out_o).max()
        blocks = gather_blocks(out_t, prog_o)
        serial, _ = selected_inverse(A, max_supernode=b)
        d = max(abs(blocks[I, J] - np.asarray(blk)).max()
                for (I, J), blk in serial.items())
        assert d <= 1e-12, d

        ref = dense_selinv_oracle(A)
        err = 0.0
        for K in range(bs.nsuper):
            err = max(err, abs(blocks[K, K]
                               - ref[K*8:(K+1)*8, K*8:(K+1)*8]).max())
            for I in bs.struct[K]:
                I = int(I)
                err = max(err, abs(blocks[I, K]
                                   - ref[I*8:(I+1)*8, K*8:(K+1)*8]).max())
        assert err < 1e-9, err
        print("OK")
    """, x64=True, timeout=600)


@pytest.mark.slow
@pytest.mark.bigmesh
def test_stream_executor_bit_identical_grid8x4():
    """The tentpole's target scale: a 32-host-device 8×4 grid
    (``bigmesh`` marker — run with ``-m bigmesh``), where the flat ring
    would execute 31 permutes every round. The gated stream executor is
    f64 bit-identical to the unrolled overlapped executor, matches the
    serial selected inverse to 1e-12, and its executed wire matches the
    simulator."""
    run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.core import sparse
        from repro.core.plan import PlanOptions
        from repro.core.simulator import executed_wire_bytes
        from repro.core.stream import stream_wire_bytes
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import (analyze_structure,
                                             build_program, gather_blocks,
                                             make_sweep_overlapped,
                                             make_sweep_stream,
                                             prepare_values)
        from repro.core.selinv import selected_inverse
        A = sparse.laplacian_2d(32, 8)
        b, pr, pc = 8, 8, 4
        bs, nb = analyze_structure(A, b, pr, pc)
        Lh_s, Dinv_s = prepare_values(A, bs, nb, b, pr, pc)
        devs = np.array(jax.devices()[:pr * pc]).reshape(pr * pc)
        mesh = Mesh(devs, ("xy",))
        Lh = jnp.asarray(Lh_s, jnp.float64)
        Dinv = jnp.asarray(Dinv_s, jnp.float64)

        def run(prog, mk):
            fn = jax.jit(shard_map(mk(prog), mesh=mesh,
                                   in_specs=(P("xy"), P("xy")),
                                   out_specs=P("xy")))
            return np.asarray(fn(Lh, Dinv))

        prog_t = build_program(bs, nb, b, pr, pc,
                               options=PlanOptions(stream=True))
        assert executed_wire_bytes(prog_t) == \\
            stream_wire_bytes(prog_t.stream_tables, b)
        out_t = run(prog_t, make_sweep_stream)
        out_o = run(build_program(bs, nb, b, pr, pc, TreeKind.SHIFTED),
                    make_sweep_overlapped)
        assert abs(out_t - out_o).max() <= 1e-12, abs(out_t - out_o).max()
        blocks = gather_blocks(out_t, prog_t)
        serial, _ = selected_inverse(A, max_supernode=b)
        d = max(abs(blocks[I, J] - np.asarray(blk)).max()
                for (I, J), blk in serial.items())
        assert d <= 1e-12, d
        print("OK")
    """, ndev=32, x64=True, timeout=600)


def test_stream_engine_session_end_to_end():
    """PlanOptions(stream=True) through the engine: cached analyze, a
    no-retrace solve hot path, batched solves bit-identical to the
    single path, and compile metrics off stats(compile=True) showing the
    stream program strictly smaller + faster-compiling than the unrolled
    overlapped program of the same structure."""
    run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine

        A = sparse.laplacian_2d(16, 8)
        PSelInvEngine.clear_cache()
        opts = PlanOptions(stream=True)
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2), options=opts)
        assert eng.program.stream_tables is not None
        again = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                      options=PlanOptions(stream=True))
        assert again is eng            # options hash in the cache key
        base = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                     options=PlanOptions())
        assert base is not eng

        # stats: schedule keys shared with the unrolled session, plus
        # the stream session's executed-wire pair; compile metrics on
        # demand
        cache_keys = {"table_bytes", "cache_engines", "cache_hits",
                      "cache_misses", "cache_evictions"}
        gauge_keys = {"solve_calls"}
        comm_keys = {"ppermute_rounds", "wire_bytes", "recv_bytes_max",
                     "recv_bytes_mean"}
        s = eng.stats()
        assert set(s) == {"peak_arena_blocks", "stream_wire_bytes",
                          "stream_shifts_per_round"} \
            | comm_keys | cache_keys | gauge_keys
        sb = base.stats()
        assert set(sb) == {"peak_arena_blocks"} | comm_keys \
            | cache_keys | gauge_keys
        for k in ("ppermute_rounds", "peak_arena_blocks",
                  "recv_bytes_max", "recv_bytes_mean"):
            assert s[k] == sb[k]       # same schedule, same arena
        assert s["stream_wire_bytes"] > 0
        # gating beats the flat-ring encoding's every-shift-every-round
        nshifts = len(eng.program.stream_tables.shifts)
        assert 0 < s["stream_shifts_per_round"] < nshifts
        # simulated == executed wire: the simulator's independent lens
        # over the gated tables agrees with the table-derived number
        from repro.core.simulator import executed_wire_bytes
        assert executed_wire_bytes(eng) == s["stream_wire_bytes"] \
            == s["wire_bytes"]
        cs = eng.stats(compile=True)
        cu = base.stats(compile=True)
        for k in ("trace_lower_ms", "compile_ms", "jaxpr_lines",
                  "hlo_bytes"):
            assert cs[k] > 0 and cu[k] > 0
        assert cs["hlo_bytes"] <= 0.5 * cu["hlo_bytes"], (cs, cu)
        assert cs["jaxpr_lines"] < cu["jaxpr_lines"]
        assert eng.compile_stats() is eng.compile_stats()   # cached

        # solve: f64 bit-identical to the unrolled overlapped engine,
        # no retrace across repeated solves, batched == loop of singles
        out = np.asarray(eng.solve(A, dtype=jnp.float64))
        out_b = np.asarray(base.solve(A, dtype=jnp.float64))
        assert abs(out - out_b).max() <= 1e-12
        t0 = eng.trace_count
        eng.solve(A, dtype=jnp.float64)
        assert eng.trace_count == t0, "stream solve retraced"
        mats = [A + sp.identity(A.shape[0]) * c for c in (0.0, 0.5)]
        outs = np.asarray(eng.solve_many(mats, dtype=jnp.float64))
        for i, M in enumerate(mats):
            d = abs(outs[i]
                    - np.asarray(eng.solve(M, dtype=jnp.float64))).max()
            assert d <= 1e-12, (i, d)

        # the executed-timeline plumbing routes through the stream tables
        sim = eng.simulate()
        assert sim.total_time == base.simulate().total_time
        print("OK")
    """, x64=True, timeout=600)
