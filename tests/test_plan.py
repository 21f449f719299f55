"""CommPlan IR tests: the single-derivation guarantees.

(a) bytes equivalence — per-rank byte counts summed over the *compiled*
    overlapped rounds (with and without a Û liveness window) equal
    ``simulator.volumes`` on the same structure/grid/tree-kind
    (simulated bytes == executed bytes);
(b) oracle — the overlapped sweep matches the serial selected inverse
    and the dense inverse on the selected pattern for several
    (pr, pc, TreeKind) combinations;
(c) overlap — the global round stream respects every producer→consumer
    round dependence, coalesces multi-block (src,dst) payloads, and
    issues fewer ppermute rounds than single-block transfers;
(d) fast-path drift — ``volumes_fast`` is bit-identical to the slow
    ``volumes`` for all four TreeKinds, including HYBRID at the
    flat/shifted boundary participant counts (24 and 25);
plus structural invariants of the level batching and the merged-round
diagnostics.
"""
import numpy as np
import pytest
import scipy.sparse as sp

from conftest import run_sub

from repro.core import sparse
from repro.core.plan import (build_plan, etree_levels,
                             exec_byte_counts, merge_round_lists,
                             peak_arena_blocks, ppermute_round_count,
                             schedule_overlapped, tree_for)
from repro.core.schedule import Grid2D
from repro.core.simulator import (round_schedule_from_overlap,
                                  simulate_schedule, volumes, volumes_fast)
from repro.core.symbolic import BlockStructure, symbolic_factorize
from repro.core.trees import HYBRID_FLAT_MAX, TreeKind, build_tree

@pytest.fixture(scope="module")
def lap_bs():
    A = sparse.laplacian_2d(12, 8)
    return A, symbolic_factorize(sp.csr_matrix(A), max_supernode=8)


@pytest.mark.parametrize("pr,pc", [(4, 2), (2, 2), (2, 4)])
@pytest.mark.parametrize("kind",
                         [TreeKind.FLAT, TreeKind.BINARY, TreeKind.SHIFTED])
def test_exec_bytes_match_volumes(lap_bs, pr, pc, kind):
    """The bytes the compiled device program moves are the bytes the
    simulator accounts — same plan, independent accounting paths — also
    when a one-level Û window recycles the arena and reshapes the
    rounds."""
    _, bs = lap_bs
    grid = Grid2D(pr, pc)
    plan = build_plan(bs, grid, kind, nb=12)
    out_e, inc_e = exec_byte_counts(schedule_overlapped(plan, window=1))
    out_v, inc_v = volumes(bs, grid, kind)
    z = np.zeros(grid.size)
    for k in ("xfer", "col-bcast"):
        np.testing.assert_allclose(out_e.get(k, z), out_v.get(k, z))
        np.testing.assert_allclose(inc_e.get(k, z), inc_v.get(k, z))
    # volumes reports reductions in broadcast orientation (§4.1 counts
    # received volume at the combining node): mirror to wire direction
    np.testing.assert_allclose(out_e.get("row-reduce", z),
                               inc_v.get("row-reduce", z))
    np.testing.assert_allclose(inc_e.get("row-reduce", z),
                               out_v.get("row-reduce", z))


@pytest.mark.parametrize("pr,pc", [(4, 2), (2, 2), (2, 4)])
@pytest.mark.parametrize("kind",
                         [TreeKind.FLAT, TreeKind.BINARY, TreeKind.SHIFTED])
def test_overlapped_bytes_match_volumes(lap_bs, pr, pc, kind):
    """Coalescing + cross-level interleaving move the same bytes in fewer
    rounds: the overlapped stream's per-rank byte counts equal the
    simulator's volumes."""
    _, bs = lap_bs
    grid = Grid2D(pr, pc)
    plan = build_plan(bs, grid, kind, nb=12)
    out_e, inc_e = exec_byte_counts(schedule_overlapped(plan))
    out_v, inc_v = volumes(bs, grid, kind)
    z = np.zeros(grid.size)
    for k in ("xfer", "col-bcast"):
        np.testing.assert_allclose(out_e.get(k, z), out_v.get(k, z))
        np.testing.assert_allclose(inc_e.get(k, z), inc_v.get(k, z))
    np.testing.assert_allclose(out_e.get("row-reduce", z),
                               inc_v.get("row-reduce", z))
    np.testing.assert_allclose(inc_e.get("row-reduce", z),
                               out_v.get("row-reduce", z))


def _overlap_boundaries(ov):
    """Round boundary of each (compute kind, level) of the stream."""
    at = {}
    for t, ops in enumerate(ov.compute_at):
        for op in ops:
            at[(op.kind, op.level)] = t
    return at


@pytest.mark.parametrize("kind", [TreeKind.FLAT, TreeKind.SHIFTED])
def test_overlapped_respects_round_dependences(lap_bs, kind):
    """Every producer→consumer dependence of the sweep holds in the
    global round sequence: a level's xfer-in/col-bcast rounds precede its
    GEMM boundary, its reduce rounds sit between GEMM and column write,
    xfer-out follows the write, diag-reduce follows the S computation,
    the diagonal write follows its reduces — and level L's GEMM fires
    only after level L-1 finished every A⁻¹ write."""
    _, bs = lap_bs
    plan = build_plan(bs, Grid2D(4, 2), kind, nb=12)
    ov = schedule_overlapped(plan)
    at = _overlap_boundaries(ov)
    nlev = len(ov.levels)

    rounds_of = {}          # (kind, level) -> list of round indices
    for t, rnd in enumerate(ov.rounds):
        for (_s, _d, k, lv, _nb) in rnd.edges:
            rounds_of.setdefault((k, lv), []).append(t)
        for (_dev, k, lv) in rnd.lmoves:
            rounds_of.setdefault((k, lv), []).append(t)

    for L in range(nlev):
        tg, tw = at[("gemm", L)], at[("write", L)]
        ts, td = at[("scomp", L)], at[("diagw", L)]
        assert tg <= tw <= ts <= td
        for k in ("xfer", "xfer-local", "col-bcast"):
            assert all(t < tg for t in rounds_of.get((k, L), []))
        assert all(tg <= t < tw for t in rounds_of.get(("row-reduce", L), []))
        for k in ("xfer-out", "xfer-out-local"):
            assert all(tw <= t < ts for t in rounds_of.get((k, L), []))
        assert all(ts <= t < td
                   for t in rounds_of.get(("diag-reduce", L), []))
        if L:
            # cross-level serialization of the A⁻¹ writes only
            prev = rounds_of.get(("xfer-out", L - 1), []) \
                + rounds_of.get(("xfer-out-local", L - 1), [])
            assert tg > at[("write", L - 1)]
            # diagw(L-1) may share gemm(L)'s boundary: compute ops within
            # one boundary execute in dependence order
            assert tg >= at[("diagw", L - 1)]
            assert all(t < tg for t in prev)

    # ...and the point of the exercise: later levels' xfer-in/col-bcast
    # traffic actually rides rounds *before* the previous level's GEMM
    # has even fired (no level barrier left)
    overlapped = [
        L for L in range(1, nlev)
        if rounds_of.get(("xfer", L), []) and
        min(rounds_of[("xfer", L)]) < at[("gemm", L - 1)]]
    assert overlapped, "no cross-level interleaving happened"


@pytest.mark.parametrize("pr,pc", [(4, 2), (2, 2)])
def test_overlapped_fewer_rounds_and_coalescing(lap_bs, pr, pc):
    """The overlapped+coalesced stream issues strictly fewer ppermute
    rounds than it moves blocks, some round carries a multi-block
    (src,dst) payload, and every round still satisfies the ppermute
    constraint (unique sources / destinations across pairs, lane count
    within the coalescing cap)."""
    _, bs = lap_bs
    plan = build_plan(bs, Grid2D(pr, pc), TreeKind.SHIFTED, nb=12)
    ov = schedule_overlapped(plan, coalesce_max=8)
    assert ppermute_round_count(ov) < sum(len(r.edges) for r in ov.rounds)
    assert any(r.width > 1 for r in ov.rounds)
    for rnd in ov.rounds:
        if not rnd.perm:        # local-copy-only rounds are legal
            assert rnd.width == 0 and not rnd.edges and rnd.lwidth
            continue
        srcs = [s for s, _ in rnd.perm]
        dsts = [d for _, d in rnd.perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        assert rnd.width <= 8
        lanes = {}
        for (s, d, _k, _lv, _nb) in rnd.edges:
            lanes[(s, d)] = lanes.get((s, d), 0) + 1
        assert lanes, rnd
        assert max(lanes.values()) == rnd.width


@pytest.mark.parametrize("window", [None, 1, 2])
def test_overlapped_u_stacks_complete_at_read_boundaries(window):
    """Replay only the comm rounds of the overlapped stream (numpy, host
    side) and check that at every GEMM *and* scomp boundary each
    participating device holds the exact Û(K,I) = L̂(I,K)ᵀ payload —
    scomp is a level's *last* Û reader, so holding there proves the
    recycled slots stay intact across the whole liveness window.

    Regression test for two dependence-keying hazards: (a) per-device
    slot keying — the per-column Û allocators share one address range,
    so equal slot numbers on different grid columns hold different
    blocks, and a slot-only key once wired a broadcast's root to the
    wrong xfer-in, shipping zeros; (b) generation keying — under slot
    recycling (window=1/2 here) the same (device, slot) hosts several
    levels' payloads, and a missing WAR anti-dependence would let a new
    generation's fill clobber a slot its previous tenant still reads.

    The arena holds no L̂ copy: xfer-in lanes read the resident input
    shard through the per-lane ``glh``/``lglh`` masks, so the replay
    keeps L̂ as a separate read-only buffer exactly like the executor."""
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(32, 8)), max_supernode=8)
    pr, pc = 4, 2
    plan = build_plan(bs, Grid2D(pr, pc), TreeKind.SHIFTED, nb=32)
    ov = schedule_overlapped(plan, window=window)
    P, nbr, nbc = pr * pc, ov.nbr, ov.nbc
    N = ov.n_ainv

    if window is not None:
        # recycling must actually alias slots across generations here
        owners = {}
        aliased = 0
        for L, lv in enumerate(ov.levels):
            for dev in range(P):
                for slot in lv.u_gather[dev]:
                    if slot == ov.trash:
                        continue
                    key = (dev, int(slot))
                    if key in owners and owners[key] != L:
                        aliased += 1
                    owners[key] = L
        assert aliased, "window set but no Û slot was ever recycled"

    # distinguishable payload per global block (I, K); L̂ is its own
    # buffer (the arena holds no copy of it)
    arena = np.zeros((P, ov.arena_blocks))
    lh = np.zeros((P, N))
    for K in range(bs.nsuper):
        for I in bs.struct[K]:
            I = int(I)
            dev = (I % pr) * pc + (K % pc)
            lh[dev, (I // pr) * nbc + K // pc] = 1000.0 * I + K

    read_at = {}
    for t, ops in enumerate(ov.compute_at):
        for op in ops:
            if op.kind in ("gemm", "scomp"):
                read_at.setdefault(t, []).append(op.level)

    def check_level(L):
        lv = ov.levels[L]
        for k, K in enumerate(lv.Ks):
            C = [int(x) for x in bs.struct[K]]
            for I in C:
                need = ({(J % pr) * pc + I % pc for J in C}
                        | {(K % pr) * pc + I % pc})
                for dev in need:
                    slot = lv.u_gather[dev, k * nbc + I // pc]
                    assert slot != ov.trash, (L, K, I, dev)
                    assert arena[dev, slot] == 1000.0 * I + K, \
                        (L, K, I, dev)

    def lane_src(snap, dev, slot, from_lh):
        return lh[dev, slot] if from_lh else snap[dev, slot]

    for t, rnd in enumerate(ov.rounds):
        for L in read_at.get(t, ()):
            check_level(L)
        if rnd.lwidth:
            snap = arena.copy()
            for dev in range(P):
                for j in range(rnd.lwidth):
                    arena[dev, rnd.lscatter[dev, j]] = lane_src(
                        snap, dev, rnd.lgather[dev, j], rnd.lglh[dev, j])
        if rnd.perm:
            snap = arena.copy()
            moved = np.zeros((P, rnd.width))
            for (s, d) in rnd.perm:
                moved[d] = [lane_src(snap, s, rnd.gather[s, j],
                                     rnd.glh[s, j])
                            for j in range(rnd.width)]
            for dev in range(P):
                for j in range(rnd.width):
                    arena[dev, rnd.scatter[dev, j]] = (
                        moved[dev, j]
                        + rnd.addm[dev, j] * snap[dev, rnd.scatter[dev, j]])
    for L in read_at.get(len(ov.rounds), ()):
        check_level(L)


def _u_write_lanes(ov):
    """Reconstruct every Û-writing lane of the compiled stream as
    (round, device, arena slot, level). Lane order inside
    ``GlobalRound.edges`` follows the (pair, lane) nesting of the
    scheduler, so the lane index recovers the scatter-table column."""
    out = []
    for t, rnd in enumerate(ov.rounds):
        lane_j = {}
        for (s, d, kind, lv, _nb) in rnd.edges:
            j = lane_j.get((s, d), 0)
            lane_j[(s, d)] = j + 1
            if kind in ("xfer", "col-bcast"):
                out.append((t, d, int(rnd.scatter[d, j]), lv))
        lane_j = {}
        for (dev, kind, lv) in rnd.lmoves:
            j = lane_j.get(dev, 0)
            lane_j[dev] = j + 1
            if kind == "xfer-local":
                out.append((t, dev, int(rnd.lscatter[dev, j]), lv))
    return out


@pytest.mark.parametrize("window", [None, 1, 2])
def test_no_live_generations_alias_a_slot(window):
    """The liveness-window property: whenever two generations (levels)
    alias the same (device, arena slot), the earlier tenant's *last
    read* precedes the later tenant's *first write*.

    Û slots: a generation is live from its first fill into the slot to
    its scomp boundary (boundary t computes before round t's comm, so
    ``scomp_boundary <= first_write_round`` is exact). Shared partial /
    S regions: generation L's occupancy [gemm(L), write(L)] /
    [scomp(L), diagw(L)] must end before generation L+1's begins —
    compute ops sharing a boundary execute in ``compute_at`` list order,
    so ties are legal only with the reader listed first."""
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(32, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=32)
    ov = schedule_overlapped(plan, window=window)
    at = {(op.kind, op.level): t for t, ops in enumerate(ov.compute_at)
          for op in ops}
    nlev = len(ov.levels)

    # ---- Û pool: per (device, slot), generations must not overlap ----
    writes = {}
    for (t, dev, slot, lv) in _u_write_lanes(ov):
        writes.setdefault((dev, slot), {}).setdefault(lv, []).append(t)
    aliased = 0
    for (dev, slot), gens in writes.items():
        order = sorted(gens)
        aliased += len(order) - 1
        for la, lb in zip(order, order[1:]):
            last_read = at[("scomp", la)]
            first_write = min(gens[lb])
            assert last_read <= first_write, \
                (dev, slot, la, lb, last_read, first_write)
    if window is not None:
        assert aliased, "window set but no Û slot hosted two generations"

    # ---- shared partial / S regions: generations ordered in time -----
    def _ordered(reader, writer, L):
        tr, tw = at[(reader, L)], at[(writer, L + 1)]
        assert tr <= tw, (reader, writer, L, tr, tw)
        if tr == tw:
            ops = ov.compute_at[tr]
            ir = ops.index(next(o for o in ops
                                if o.kind == reader and o.level == L))
            iw = ops.index(next(o for o in ops
                                if o.kind == writer and o.level == L + 1))
            assert ir < iw, (reader, writer, L)

    for L in range(nlev - 1):
        _ordered("write", "gemm", L)     # partial region: last read vs
        _ordered("diagw", "scomp", L)    # next write; same for S region


@pytest.mark.parametrize("nx,max_rounds", [(16, 28), (32, 35)])
def test_recycled_arena_peak_and_rounds(nx, max_rounds):
    """The acceptance envelope of the arena recycling + copy-free L̂
    gathers at grid 4×2: the ppermute round counts hold the
    coalesced-overlap wins (28 @ nb=16, 35 @ nb=32 — the shift-aware
    packer's offset grouping pays one round here at 4×2 and wins two
    back at 8×4, for a stream wire cut from ~36× to ~1.6× unrolled), a
    one-level window never grows the peak footprint (arena + the
    resident input L̂ shard), and the schedule simulator carries the
    peak so the bench trajectory can regression-guard it."""
    bs = symbolic_factorize(
        sp.csr_matrix(sparse.laplacian_2d(nx, 8)), max_supernode=8)
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=nx)
    ov = schedule_overlapped(plan)
    assert ppermute_round_count(ov) <= max_rounds
    sim = simulate_schedule(round_schedule_from_overlap(ov, plan))
    assert sim.peak_arena_blocks == peak_arena_blocks(ov)
    # a tighter window trades rounds for an even smaller arena but must
    # never lose correctness or the memory bound
    ov1 = schedule_overlapped(plan, window=1)
    assert peak_arena_blocks(ov1) <= peak_arena_blocks(ov)


def _dense_chain_bs(ns: int, w: int = 1) -> BlockStructure:
    """Dense lower-triangular block structure: struct(K) = {K+1..ns-1}
    (a path etree) — every participant count from ns down to 2 appears,
    which pins the HYBRID flat/shifted boundary exactly."""
    struct = [np.arange(K + 1, ns, dtype=np.int64) for K in range(ns)]
    return BlockStructure(
        offsets=np.arange(ns + 1, dtype=np.int64) * w,
        struct=struct, a_struct=struct,
        parent=np.array([K + 1 if K + 1 < ns else -1 for K in range(ns)],
                        dtype=np.int64))


@pytest.mark.parametrize("pr,pc", [(HYBRID_FLAT_MAX + 2, 1),
                                   (1, HYBRID_FLAT_MAX + 2)])
@pytest.mark.parametrize("kind", list(TreeKind))
def test_volumes_fast_bit_identical_at_hybrid_boundary(pr, pc, kind):
    """``volumes_fast`` must agree bit-for-bit with the slow tree-walking
    ``volumes`` for every TreeKind — in particular HYBRID straddling the
    flat→shifted threshold: the dense chain on a 26-rank axis issues
    collectives with 26, 25, 24, ... participants, so both sides of
    ``HYBRID_FLAT_MAX = 24`` (and the boundary counts 24/25 themselves)
    are exercised with the tag-derived shifted rotations."""
    bs = _dense_chain_bs(HYBRID_FLAT_MAX + 2)
    grid = Grid2D(pr, pc)
    out, _ = volumes(bs, grid, kind)
    fast = volumes_fast(bs, grid, kind)
    z = np.zeros(grid.size)
    np.testing.assert_array_equal(out.get("col-bcast", z),
                                  fast["col-bcast"])
    np.testing.assert_array_equal(out.get("row-reduce", z),
                                  fast["row-reduce"])


def test_tree_for_hybrid_participant_dispatch():
    """``tree_for`` is the per-collective HYBRID dispatch keyed on
    participant count (paper §4.2): at or below ``HYBRID_FLAT_MAX``
    participants the collective is the *memoized* flat tree — the very
    object the FLAT path returns, tag-independent — and one participant
    above the boundary it becomes the tag-seeded shifted-binary tree
    with logarithmic depth."""
    root = 5
    at_max = tuple(range(HYBRID_FLAT_MAX))            # 24 participants
    t_h = tree_for(TreeKind.HYBRID, root, at_max, tag=7)
    t_f = tree_for(TreeKind.FLAT, root, at_max, tag=3)
    assert t_h is t_f                      # same memoized flat object
    assert t_h == build_tree(TreeKind.FLAT, root,
                             [r for r in at_max if r != root])
    # flat: the root feeds every receiver directly (single fan-out)
    assert t_h.children == ((root, tuple(r for r in at_max
                                         if r != root)),)
    # different tags at/below the boundary: still the one flat tree
    assert tree_for(TreeKind.HYBRID, root, at_max, tag=11) is t_h

    over = tuple(range(HYBRID_FLAT_MAX + 1))          # 25 participants
    t_h25 = tree_for(TreeKind.HYBRID, root, over, tag=7)
    assert t_h25 == build_tree(TreeKind.HYBRID, root,
                               [r for r in over if r != root], tag=7)
    # shifted-binary: internal fan-out, logarithmic receive rounds —
    # strictly shallower than the flat tree's serial send chain
    assert len(t_h25.children) > 1
    assert 1 < t_h25.depth() < t_h.depth()
    # above the boundary the tag decorrelates concurrent collectives
    assert any(tree_for(TreeKind.HYBRID, root, over, tag=tg) != t_h25
               for tg in (8, 9, 10))


def test_hybrid_kind_bit_identical_below_boundary():
    """Numeric half of the boundary test: on an 8-device 4×2 grid every
    collective has ≤ 8 < ``HYBRID_FLAT_MAX`` participants, so a HYBRID
    plan must lower to the *same rounds* as a FLAT plan and both stream
    and overlapped executors must produce f64 bit-identical (drift 0.0)
    results across the kinds."""
    run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.core import sparse
        from repro.core.plan import PlanOptions
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import (analyze_structure,
                                             build_program,
                                             make_sweep_overlapped,
                                             make_sweep_stream,
                                             prepare_values)
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

        outs = {}
        for kind in (TreeKind.HYBRID, TreeKind.FLAT):
            outs[kind, "st"] = run(
                build_program(bs, nb, b, pr, pc, kind,
                              options=PlanOptions(stream=True,
                                                  kind=kind)),
                make_sweep_stream)
            outs[kind, "ov"] = run(
                build_program(bs, nb, b, pr, pc, kind),
                make_sweep_overlapped)
        for ex in ("st", "ov"):
            d = abs(outs[TreeKind.HYBRID, ex]
                    - outs[TreeKind.FLAT, ex]).max()
            assert d == 0.0, (ex, d)
        print("OK")
    """, x64=True)


def test_levels_are_independent(lap_bs):
    """Same-level supernodes never appear in each other's struct — the
    condition that makes the level batching a legal reordering of the
    reverse-elimination sweep."""
    _, bs = lap_bs
    level = etree_levels(bs)
    for K in range(bs.nsuper):
        for I in bs.struct[K]:
            assert level[int(I)] < level[K]   # struct(K) ⊆ ancestors(K)


def test_plan_padding_supernodes(lap_bs):
    """Grid padding adds diag-only supernodes and no communication."""
    _, bs = lap_bs
    plan = build_plan(bs, Grid2D(3, 2), TreeKind.SHIFTED, nb=18)
    assert plan.nb == 18
    assert set(range(bs.nsuper, 18)) <= set(plan.diag_only)
    assert all(op.supernode < bs.nsuper for op in plan.ops)
    ov = schedule_overlapped(plan)
    assert len(ov.diag_set_root) == len(plan.diag_only)


def test_packed_rounds_respect_ppermute_constraint(lap_bs):
    """Every compiled round has unique sources and destinations."""
    _, bs = lap_bs
    plan = build_plan(bs, Grid2D(4, 2), TreeKind.SHIFTED, nb=12)
    nrounds = 0
    for rnd in schedule_overlapped(plan).rounds:
        srcs = [s for s, _ in rnd.perm]
        dsts = [d for _, d in rnd.perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        nrounds += bool(rnd.perm)
    assert nrounds > 0


def test_merge_round_lists_collision_diagnostics():
    """Non-disjoint trees raise ValueError naming the colliding pairs."""
    t1 = build_tree(TreeKind.FLAT, 0, [1, 2])
    t2 = build_tree(TreeKind.FLAT, 0, [3])
    per_tree = [t1.bcast_rounds(), t2.bcast_rounds()]
    with pytest.raises(ValueError) as ei:
        merge_round_lists(per_tree, "bcast")
    msg = str(ei.value)
    assert "round 0" in msg and "(0, 1)" in msg and "(0, 3)" in msg


def test_batched_rounds_uses_shared_merge():
    """treecomm.batched_rounds delegates to the IR merge (disjoint trees
    merge; overlapping trees get the diagnostic ValueError)."""
    from repro.comm.treecomm import batched_rounds
    t1 = build_tree(TreeKind.BINARY, 0, [1, 2, 3])
    t2 = build_tree(TreeKind.BINARY, 0, [1, 2, 3])
    merged = batched_rounds([(t1, 0), (t2, 4)], "bcast")
    flat = [e for rnd in merged for e in rnd]
    assert len(flat) == 6 and max(max(s, d) for s, d in flat) == 7
    with pytest.raises(ValueError):
        batched_rounds([(t1, 0), (t2, 0)], "bcast")


def test_ir_sweep_matches_oracle_multi_grid():
    """The overlapped IR sweep (the default executor) reproduces the
    serial selected inverse (``selinv.selected_inverse``) to 1e-12 and
    the dense inverse on the selected pattern for several grid shapes /
    tree kinds."""
    run_sub("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import gather_blocks
        from repro.core.selinv import dense_selinv_oracle, selected_inverse
        A = sparse.laplacian_2d(12, 8)
        ref = dense_selinv_oracle(A)
        serial, _ = selected_inverse(A, max_supernode=8)
        for (pr, pc, kind) in ((2, 4, TreeKind.SHIFTED),
                               (2, 2, TreeKind.FLAT),
                               (4, 2, TreeKind.BINARY)):
            eng = PSelInvEngine.analyze(A, b=8, grid=Grid(pr, pc),
                                        options=PlanOptions(kind=kind))
            out = np.asarray(eng.solve(A, dtype=jnp.float64))
            blocks = gather_blocks(out, eng)
            d = max(abs(blocks[I, J] - np.asarray(blk)).max()
                    for (I, J), blk in serial.items())
            assert d < 1e-12, (pr, pc, kind, d)
            bs = eng.bs
            err = 0.0
            for K in range(bs.nsuper):
                err = max(err, abs(blocks[K, K]
                                   - ref[K*8:(K+1)*8, K*8:(K+1)*8]).max())
                for I in bs.struct[K]:
                    I = int(I)
                    err = max(err, abs(blocks[I, K]
                                       - ref[I*8:(I+1)*8, K*8:(K+1)*8]).max())
            assert err < 1e-9, (pr, pc, kind, err)
        print("OK")
    """, x64=True)


def test_overlapped_recycled_matches_serial_nb32():
    """End-to-end oracle under *forced* Û slot reuse: nb=32 on grid 4×2
    with window=1 (every level recycles the previous level's compact Û
    slots, plus the always-shared partial/S regions) must match the
    serial selected inverse (``selinv.selected_inverse``) bit-tight
    (≤1e-12 in f64) and the dense inverse on the selected pattern — the
    executed proof that the generation anti-dependences make aliasing
    safe, not just the host replay."""
    run_sub("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.core import sparse
        from repro.core.trees import TreeKind
        from repro.core.pselinv_dist import (analyze_structure,
                                             build_program,
                                             make_sweep_overlapped,
                                             prepare_values, gather_blocks)
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

        prog_w = build_program(bs, nb, b, pr, pc, TreeKind.SHIFTED,
                               window=1)
        assert prog_w.overlap_plan.arena_blocks < 400  # recycled arena
        out_w = run(prog_w, make_sweep_overlapped)
        blocks = gather_blocks(out_w, prog_w)
        serial, _ = selected_inverse(A, max_supernode=b)
        d = max(abs(blocks[I, J] - np.asarray(blk)).max()
                for (I, J), blk in serial.items())
        assert d < 1e-12, d

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
