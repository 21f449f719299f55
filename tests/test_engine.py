"""PSelInvEngine session API tests: the analyze-once / solve-many
contract.

(a) structure cache — a second ``analyze`` with an identical (structure,
    b, grid, options) returns the *same* engine object, compiled program
    included; different options miss;
(b) no retrace — repeated ``solve`` calls of one shape class reuse the
    jitted sweep (trace counter flat after warmup), including the
    batched shape;
(c) batching — ``solve`` over a leading batch axis of B same-structure
    matrices is bit-identical (f64, ≤1e-12; observed exact) to a Python
    loop of single solves;
(d) host prep — the batched BLAS-3 factorization, at B=1 and above,
    matches the supernodal LU + triangular solves it replaced.
"""
import numpy as np
import pytest

from conftest import run_sub

from repro.core import sparse
from repro.core.engine import (Grid, PlanOptions, PSelInvEngine,
                               structure_key)
from repro.core.schedule import Grid2D


def test_grid_is_the_session_alias():
    """The engine API's Grid *is* schedule.Grid2D — one grid type, no
    parallel definition to drift."""
    assert Grid is Grid2D


def test_structure_key_content_hash():
    """Equal structures (independently symbolic-factorized) hash equal;
    a different sparsity structure hashes different."""
    import scipy.sparse as sp
    from repro.core.symbolic import symbolic_factorize
    A = sparse.laplacian_2d(12, 8)
    bs1 = symbolic_factorize(sp.csr_matrix(A), max_supernode=8)
    bs2 = symbolic_factorize(sp.csr_matrix(A + sp.identity(A.shape[0])),
                             max_supernode=8)   # same pattern, new values
    bs3 = symbolic_factorize(sp.csr_matrix(sparse.laplacian_2d(16, 8)),
                             max_supernode=8)
    assert structure_key(bs1) == structure_key(bs2)
    assert structure_key(bs1) != structure_key(bs3)


def test_engine_structure_cache_and_no_retrace():
    """Cache-hit + retrace contract, executed on 8 devices: the second
    analyze of an identical structure is a cache hit returning the same
    session; solve re-traces neither across repeated single solves nor
    across repeated batched solves of one shape."""
    run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine

        A = sparse.laplacian_2d(12, 8)
        PSelInvEngine.clear_cache()
        e1 = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                   options=PlanOptions())
        # same structure, different values, independently analyzed
        e2 = PSelInvEngine.analyze(A + sp.identity(A.shape[0]), b=8,
                                   grid=Grid(4, 2), options=PlanOptions())
        assert e2 is e1, "identical structure must return the cached engine"
        assert e2.program is e1.program
        assert PSelInvEngine.cache_hits == 1
        assert PSelInvEngine.cache_misses == 1
        # options are part of the key: a different window is a new session
        e3 = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                   options=PlanOptions(window=2))
        assert e3 is not e1
        assert PSelInvEngine.cache_misses == 2

        # ---- solve does not retrace (trace counter flat after warmup)
        v = e1.prepare_values(A)
        out1 = e1.solve(v)
        t0 = e1.trace_count
        assert t0 >= 1
        out2 = e1.solve(v)
        assert e1.trace_count == t0, "second solve of one shape retraced"
        assert np.asarray(out1).shape == np.asarray(out2).shape

        # batched shape class: one extra trace, then flat
        from repro.core.engine import stack_values
        vb = stack_values([v, v, v])
        e1.solve(vb)
        tb = e1.trace_count
        e1.solve(vb)
        assert e1.trace_count == tb, "second batched solve retraced"
        print("OK")
    """)


def test_engine_batched_solve_matches_single_loop():
    """solve(values[B]) over B same-structure matrices is bit-identical
    (f64) to a loop of single solves, and matches the dense oracle on
    the selected pattern for every batch member."""
    run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine
        from repro.core.pselinv_dist import gather_blocks
        from repro.core.selinv import dense_selinv_oracle

        A = sparse.laplacian_2d(12, 8)
        mats = [A + sp.identity(A.shape[0]) * c for c in (0.0, 0.25, 1.0)]
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                    options=PlanOptions())
        outs_b = np.asarray(eng.solve_many(mats, dtype=jnp.float64))
        assert outs_b.shape[0] == 3
        for i, M in enumerate(mats):
            single = np.asarray(eng.solve(M, dtype=jnp.float64))
            d = abs(outs_b[i] - single).max()
            assert d <= 1e-12, (i, d)
            ref = dense_selinv_oracle(M)
            blocks = gather_blocks(outs_b[i], eng)   # engine accepted
            bs = eng.bs
            err = 0.0
            for K in range(bs.nsuper):
                err = max(err, abs(blocks[K, K]
                                   - ref[K*8:(K+1)*8, K*8:(K+1)*8]).max())
                for I in bs.struct[K]:
                    I = int(I)
                    err = max(err, abs(blocks[I, K]
                                       - ref[I*8:(I+1)*8, K*8:(K+1)*8]).max())
            assert err < 1e-9, (i, err)
        print("OK")
    """, x64=True)


def test_engine_simulate_and_stats():
    """engine.simulate()/round_schedule() derive the executed timeline
    from the cached program without re-lowering, and simulate_schedule
    accepts the engine/program directly (no loose (exec, plan) args)."""
    from repro.core.plan import peak_arena_blocks, ppermute_round_count
    from repro.core.simulator import (RoundSchedule, round_schedule_of,
                                      simulate_schedule)
    A = sparse.laplacian_2d(12, 8)
    PSelInvEngine.clear_cache()
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                options=PlanOptions())
    rs = eng.round_schedule()
    assert isinstance(rs, RoundSchedule)
    assert eng.round_schedule() is rs          # cached, not re-lowered
    sim = eng.simulate()
    assert sim.peak_arena_blocks == peak_arena_blocks(
        eng.program.overlap_plan)
    st = eng.stats()
    assert st["ppermute_rounds"] == ppermute_round_count(
        eng.program.overlap_plan)
    assert st["peak_arena_blocks"] == sim.peak_arena_blocks
    # cache-health counters ride along (the serving layer reads them)
    assert st["cache_engines"] == len(PSelInvEngine._cache)
    assert st["cache_hits"] == PSelInvEngine.cache_hits
    assert st["cache_misses"] == PSelInvEngine.cache_misses
    assert st["cache_evictions"] == PSelInvEngine.cache_evictions
    assert st["table_bytes"] == eng.table_bytes() > 0
    # simulate_schedule takes the engine (or program) and derives the
    # schedule itself
    sim2 = simulate_schedule(eng)
    assert sim2.total_time == sim.total_time
    assert round_schedule_of(eng.program).peak_arena_blocks == \
        sim.peak_arena_blocks


def test_engine_rejects_bad_inputs():
    """analyze validates grid vs devices (the canonical diagnostic) and
    solve validates value rank; prepare_values rejects a wrong-size
    matrix instead of silently mis-slicing — and, crucially, a same-size
    matrix whose sparsity pattern escapes the analyzed structure (the
    structured factorization would silently truncate it into the
    selected inverse of a different matrix)."""
    import scipy.sparse as sp
    A = sparse.laplacian_2d(12, 8)
    with pytest.raises(ValueError, match=r"grid 64x64 needs 4096 devices"):
        PSelInvEngine.analyze(A, b=8, grid=Grid(64, 64))
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                options=PlanOptions())
    with pytest.raises(ValueError, match=r"rank 5 .* rank 6"):
        eng.solve((np.zeros((4, 4)), np.zeros((4, 4))), dtype=None)
    with pytest.raises(ValueError, match=r"does not match the analyzed"):
        eng.prepare_values(sparse.laplacian_2d(16, 8))
    B = sp.lil_matrix(A)
    B[0, 95] = B[95, 0] = 1.0           # same n, out-of-structure block
    with pytest.raises(ValueError, match=r"outside the analyzed block"):
        eng.prepare_values(B)
    # same pattern, different values still flows through the guard
    eng.prepare_values(A + sp.identity(A.shape[0]) * 0.5)


def test_engine_cache_eviction_bound():
    """The structure cache is LRU-bounded (a long-lived server over a
    stream of distinct structures must not pin every session forever):
    exceeding cache_max evicts the least-recently-used session, and
    re-analyzing an evicted structure builds a fresh engine."""
    PSelInvEngine.clear_cache()
    old = PSelInvEngine.cache_max
    PSelInvEngine.cache_max = 2
    try:
        engines = [PSelInvEngine.analyze(sparse.laplacian_2d(nx, 8),
                                         b=8, grid=Grid(1, 1),
                                         options=PlanOptions())
                   for nx in (4, 6, 8)]
        assert len(PSelInvEngine._cache) == 2
        again = PSelInvEngine.analyze(sparse.laplacian_2d(8, 8), b=8,
                                      grid=Grid(1, 1),
                                      options=PlanOptions())
        assert again is engines[2]      # newest still cached
        fresh = PSelInvEngine.analyze(sparse.laplacian_2d(4, 8), b=8,
                                      grid=Grid(1, 1),
                                      options=PlanOptions())
        assert fresh is not engines[0]  # oldest was evicted
    finally:
        PSelInvEngine.cache_max = old
        PSelInvEngine.clear_cache()


def test_engine_cache_lru_hit_keeps_session_warm():
    """A cache *hit* moves the session to the back of the eviction
    queue: with cache_max=2, re-hitting the oldest of two sessions
    makes the *other* one the eviction victim — the serving layer's hot
    structures stay resident however old they are."""
    PSelInvEngine.clear_cache()
    old = PSelInvEngine.cache_max
    PSelInvEngine.cache_max = 2
    try:
        e4 = PSelInvEngine.analyze(sparse.laplacian_2d(4, 8), b=8,
                                   grid=Grid(1, 1), options=PlanOptions())
        PSelInvEngine.analyze(sparse.laplacian_2d(6, 8), b=8,
                              grid=Grid(1, 1), options=PlanOptions())
        # hit the older session: under FIFO it would still be evicted
        # next; under LRU the hit re-warms it
        assert PSelInvEngine.analyze(sparse.laplacian_2d(4, 8), b=8,
                                     grid=Grid(1, 1),
                                     options=PlanOptions()) is e4
        PSelInvEngine.analyze(sparse.laplacian_2d(8, 8), b=8,
                              grid=Grid(1, 1), options=PlanOptions())
        assert PSelInvEngine.cache_evictions >= 1
        again = PSelInvEngine.analyze(sparse.laplacian_2d(4, 8), b=8,
                                      grid=Grid(1, 1),
                                      options=PlanOptions())
        assert again is e4, "the re-hit session was evicted (FIFO?)"
    finally:
        PSelInvEngine.cache_max = old
        PSelInvEngine.clear_cache()


def test_engine_cache_byte_bound_eviction():
    """The size-aware bound: with cache_max_bytes below two sessions'
    summed table footprint, inserting the second evicts the first even
    though the session *count* is under cache_max — but the newest
    session itself always stays (one over-budget structure must still
    solve)."""
    PSelInvEngine.clear_cache()
    old_max, old_bytes = (PSelInvEngine.cache_max,
                          PSelInvEngine.cache_max_bytes)
    try:
        e1 = PSelInvEngine.analyze(sparse.laplacian_2d(4, 8), b=8,
                                   grid=Grid(1, 1), options=PlanOptions())
        assert e1.table_bytes() > 0
        PSelInvEngine.cache_max_bytes = e1.table_bytes()  # room for ~one
        ev0 = PSelInvEngine.cache_evictions
        e2 = PSelInvEngine.analyze(sparse.laplacian_2d(6, 8), b=8,
                                   grid=Grid(1, 1), options=PlanOptions())
        assert PSelInvEngine.cache_evictions == ev0 + 1
        assert list(PSelInvEngine._cache.values()) == [e2]
        assert PSelInvEngine.cache_bytes() == e2.table_bytes()
        # the lone over-budget session is never evicted by its own insert
        assert e2.table_bytes() > PSelInvEngine.cache_max_bytes \
            or len(PSelInvEngine._cache) == 1
    finally:
        PSelInvEngine.cache_max = old_max
        PSelInvEngine.cache_max_bytes = old_bytes
        PSelInvEngine.clear_cache()


def test_engine_bucketed_solve_shares_pow2_programs():
    """bucket=True bounds the compiled-program population: organic batch
    sizes 3, 5, 13 ride the B=4, 8, 16 programs (three traces), and
    later exact power-of-2 batches add none — while every padded result
    still matches its unbatched solve."""
    run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import (Grid, PlanOptions, PSelInvEngine,
                                       bucket_size)

        A = sparse.laplacian_2d(12, 8)
        I = sp.identity(A.shape[0])
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                    options=PlanOptions())
        singles = {}
        t0 = eng.trace_count
        for B in (3, 5, 13):
            mats = [A + 0.1 * (B + i) * I for i in range(B)]
            out = np.asarray(eng.solve_many(mats, dtype=jnp.float64,
                                            bucket=True))
            assert out.shape[0] == B, out.shape      # pad sliced off
            for i in (0, B - 1):
                ref = np.asarray(eng.solve(mats[i], dtype=jnp.float64))
                assert abs(out[i] - ref).max() <= 1e-12
        assert eng.trace_count == t0 + 3 + 1, (
            "expected one batched trace per bucket {4, 8, 16} plus the "
            f"rank-5 single-solve trace, got {eng.trace_count - t0}")
        # exact power-of-2 batches reuse those same programs: no traces
        t1 = eng.trace_count
        for B in (4, 8, 16):
            mats = [A + 0.01 * (B + i) * I for i in range(B)]
            eng.solve_many(mats, dtype=jnp.float64, bucket=True)
        assert eng.trace_count == t1, "pow2 batches retraced"
        print("OK")
    """, x64=True)


def _reference_blocks(A, bs, nb, b):
    """The host prep's math as the supernodal LU gives it: L̂ from
    ``supernodal_lu.factorize`` + ``selinv.normalize_factors``, D⁻¹(K,K)
    = U_KK⁻¹·L_KK⁻¹ by triangular solves, identity D⁻¹ on the padding
    supernodes — dense (nb, nb, b, b) grids."""
    import scipy.linalg as sla
    import scipy.sparse as sp
    from repro.core.selinv import normalize_factors
    from repro.core.supernodal_lu import factorize
    lu = factorize(sp.csr_matrix(A), bs=bs)
    Lhat, _ = normalize_factors(lu)
    Lh = np.zeros((nb, nb, b, b))
    Dinv = np.zeros((nb, nb, b, b))
    for (I, K), blk in Lhat.items():
        Lh[I, K] = np.asarray(blk)
    for K in range(bs.nsuper):
        linv = sla.solve_triangular(np.asarray(lu.Ldiag[K]), np.eye(b),
                                    lower=True, unit_diagonal=True)
        Dinv[K, K] = sla.solve_triangular(np.asarray(lu.Udiag[K]), linv,
                                          lower=False)
    for K in range(bs.nsuper, nb):
        Dinv[K, K] = np.eye(b)
    return Lh, Dinv


def _prepared_blocks(mats, b, grid, tmp_path):
    """``engine.prepare_values`` of each matrix at ``grid``, gathered
    to dense (nb, nb, b, b) grids: in this process at 1×1, in a child
    on a host mesh of ``pr*pc`` devices otherwise."""
    from repro.core.pselinv_dist import gather_blocks
    pr, pc = grid
    if grid == (1, 1):
        eng = PSelInvEngine.analyze(mats[0], b=b, grid=Grid(1, 1),
                                    options=PlanOptions())
        return [(gather_blocks(v.Lh, eng), gather_blocks(v.Dinv, eng))
                for v in map(eng.prepare_values, mats)]
    import scipy.sparse as sp
    for i, M in enumerate(mats):
        sp.save_npz(tmp_path / f"A{i}.npz", sp.csr_matrix(M))
    run_sub(f"""
        import numpy as np, scipy.sparse as sp
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine
        from repro.core.pselinv_dist import gather_blocks
        mats = [sp.load_npz("{tmp_path}/A%d.npz" % i)
                for i in range({len(mats)})]
        eng = PSelInvEngine.analyze(mats[0], b={b}, grid=Grid({pr}, {pc}),
                                    options=PlanOptions())
        for i, M in enumerate(mats):
            v = eng.prepare_values(M)
            np.save("{tmp_path}/Lh%d.npy" % i, gather_blocks(v.Lh, eng))
            np.save("{tmp_path}/Dinv%d.npy" % i,
                    gather_blocks(v.Dinv, eng))
    """, ndev=pr * pc, x64=True)
    return [(np.load(tmp_path / f"Lh{i}.npy"),
             np.load(tmp_path / f"Dinv{i}.npy")) for i in range(len(mats))]


def _assert_blocks_match(got, ref):
    """≤1e-12 per block, absolute and relative to the block's largest
    entry (so a structurally zero block must come out exactly zero)."""
    assert got.shape == ref.shape
    err = abs(got - ref).max(axis=(-2, -1))
    assert err.max() <= 1e-12
    assert (err <= 1e-12 * abs(ref).max(axis=(-2, -1))).all()


@pytest.mark.parametrize("nx,ny,b,shifts,grid", [
    pytest.param(12, 8, 8, (0.0, 0.25, 1.0, 2.0), (1, 1),
                 id="12-8-8-shifts0"),
    # b=16: supernodes whose struct(K) cliques hold 3 and 4 members, so
    # the Schur update's (|C|·b, b) @ (b, |C|·b) GEMM spans several blocks
    pytest.param(16, 16, 16, (0.5,), (1, 1), id="16-16-16-shifts1"),
    pytest.param(16, 16, 16, (0.0, 1.5), (1, 1), id="16-16-16-shifts2"),
    pytest.param(16, 16, 16, (0.0, 0.25, 1.0, 2.0), (1, 1),
                 id="16-16-16-shifts3"),
    # 9 supernodes padded to 10 (2×2) and 12 (4×2): block-cyclic shards
    # with identity D⁻¹ on the padding supernodes
    pytest.param(9, 8, 8, (0.0, 1.0), (2, 2), id="9-8-8-grid2x2"),
    pytest.param(9, 8, 8, (0.0, 1.0), (4, 2), id="9-8-8-grid4x2"),
])
def test_prepare_values_many_matches_per_matrix_path(nx, ny, b, shifts,
                                                     grid, tmp_path):
    """The stacked host factorization is numerically the supernodal LU
    + triangular solves it replaced: prepare_values_many over shifted
    copies, and engine.prepare_values (B=1) of each, match the
    reference to ≤1e-12 (f64), absolute and relative to each block's
    largest entry, for batches of 1, 2 and 4, cliques of up to 4
    supernodes and grids that pad the supernode count; a bad-pattern
    member fails with its batch index named, while one matrix alone
    fails with the pattern check's own message."""
    import scipy.sparse as sp
    from repro.core.pselinv_dist import analyze_structure, gather_blocks
    A = sparse.laplacian_2d(nx, ny)
    I_A = sp.identity(A.shape[0])
    mats = [A + c * I_A for c in shifts]
    bs, nb = analyze_structure(A, b, *grid)
    refs = [_reference_blocks(M, bs, nb, b) for M in mats]
    if grid != (1, 1):
        assert nb > bs.nsuper
    for got, ref in zip(_prepared_blocks(mats, b, grid, tmp_path), refs,
                        strict=True):
        for g, r in zip(got, ref):
            _assert_blocks_match(g, r)
    if grid != (1, 1):
        return
    eng = PSelInvEngine.analyze(A, b=b, grid=Grid(1, 1),
                                options=PlanOptions())
    if b == 16:
        assert max(len(s) for s in eng.bs.struct) >= 3
    many = eng.prepare_values_many(mats)
    for i, (Lh, Dinv) in enumerate(refs):
        _assert_blocks_match(gather_blocks(many.Lh[i], eng), Lh)
        _assert_blocks_match(gather_blocks(many.Dinv[i], eng), Dinv)
    # a member whose pattern escapes the structure names its index
    n = A.shape[0]
    B = sp.lil_matrix(A)
    B[0, n - 1] = B[n - 1, 0] = 1.0
    bad = [mats[0]] * 2 + [sp.csr_matrix(B)]
    with pytest.raises(ValueError,
                       match=r"matrix 2 of 3:.*outside the analyzed"):
        eng.prepare_values_many(bad)
    with pytest.raises(ValueError, match=r"^matrix has \d+ nonzero"):
        eng.prepare_values(B)


def _split_entries(A, parts=3):
    """``A`` as a CSR whose every stored entry is split into ``parts``
    duplicates (uneven shares, so their sum rounds) with each row's
    indices reversed: unsorted, with duplicates (for ``parts`` > 1), the
    same matrix."""
    import scipy.sparse as sp
    A = sp.csr_matrix(A)
    w = np.arange(1, parts + 1) / (parts * (parts + 1) / 2)
    rows = []
    for r in range(A.shape[0]):
        lo, hi = A.indptr[r], A.indptr[r + 1]
        idx = np.tile(A.indices[lo:hi][::-1], parts)
        val = np.concatenate([s * A.data[lo:hi][::-1] for s in w])
        rows.append((idx, val))
    indptr = np.cumsum([0] + [len(i) for i, _ in rows])
    return sp.csr_matrix((np.concatenate([v for _, v in rows]),
                          np.concatenate([i for i, _ in rows]), indptr),
                         shape=A.shape)


def _densified_workspace(csr, nb0, b, span):
    """The block workspace as a dense copy of each matrix gives it:
    ``todense`` blocked to ``(B, nb0, nb0, b, b)`` and cast to f64."""
    W = np.stack([np.asarray(M.todense()) for M in csr])
    return (W.reshape(len(csr), nb0, b, nb0, b).transpose(0, 1, 3, 2, 4)
            .astype(np.float64, copy=True))


@pytest.mark.parametrize("form", ["csr", "csr_duplicates_unsorted",
                                  "csr_unsorted", "coo", "ndarray",
                                  "csr_f32"])
def test_prepare_values_many_scatter_is_bitwise_the_densified_workspace(
        monkeypatch, form):
    """The batched prep scatters each matrix's stored entries straight
    into its block workspace: L̂ and D⁻¹ are bitwise what the workspace
    built from a dense copy of each matrix gives, for every input form
    the pattern check accepts (duplicates summed in storage order,
    unsorted indices, COO, dense, f32 cast to f64), and the caller's
    arrays are left as they were."""
    import scipy.sparse as sp
    from repro.core import pselinv_dist
    A = sparse.laplacian_2d(16, 16)
    I_A = sp.identity(A.shape[0], format="csr")
    base = [A + c * I_A for c in (0.0, 0.5, 1.25)]
    make = {"csr": lambda M: M,
            "csr_duplicates_unsorted": _split_entries,
            "csr_unsorted": lambda M: _split_entries(M, 1),
            "coo": lambda M: sp.coo_matrix(_split_entries(M, 2)),
            "ndarray": lambda M: M.toarray(),
            "csr_f32": lambda M: M.astype(np.float32)}[form]
    mats = [make(M) for M in base]
    if form.startswith("csr_") and "unsorted" in form:
        assert not any(M.has_sorted_indices for M in mats)
    eng = PSelInvEngine.analyze(A, b=16, grid=Grid(1, 1),
                                options=PlanOptions())
    before = [tuple(np.copy(a) for a in (M.data, M.indices, M.indptr))
              for M in mats if sp.issparse(M) and M.format == "csr"]
    got = eng.prepare_values_many(mats)
    monkeypatch.setattr(pselinv_dist, "_scatter_blocks",
                        _densified_workspace)
    ref = eng.prepare_values_many(mats)
    assert np.array_equal(got.Lh, ref.Lh)
    assert np.array_equal(got.Dinv, ref.Dinv)
    after = [(M.data, M.indices, M.indptr)
             for M in mats if sp.issparse(M) and M.format == "csr"]
    for was, now in zip(before, after, strict=True):
        for x, y in zip(was, now):
            assert x.dtype == y.dtype and np.array_equal(x, y)


def test_prepare_values_many_never_densifies(monkeypatch):
    """With scipy's ``todense`` and ``toarray`` made to raise, the
    batched prep still returns the same shards: no n×n dense copy of a
    matrix is ever made on this path."""
    import scipy.sparse as sp
    A = sparse.laplacian_2d(16, 16)
    mats = [A + c * sp.identity(A.shape[0]) for c in (0.0, 1.0)]
    mats.append(_split_entries(mats[0]))
    eng = PSelInvEngine.analyze(A, b=16, grid=Grid(1, 1),
                                options=PlanOptions())
    ref = eng.prepare_values_many(mats)

    def dense(*a, **kw):
        raise AssertionError("densified a sparse matrix")

    for cls in (sp.csr_matrix, sp.csr_array, sp.coo_matrix, sp.coo_array,
                sp.csc_matrix, sp.csc_array):
        monkeypatch.setattr(cls, "todense", dense)
        monkeypatch.setattr(cls, "toarray", dense)
    with pytest.raises(AssertionError, match="densified"):
        mats[0].todense()
    got = eng.prepare_values_many(mats)
    assert np.array_equal(got.Lh, ref.Lh)
    assert np.array_equal(got.Dinv, ref.Dinv)
