"""Serving-layer tests: structure-keyed coalescing, batch windows,
failure isolation, timeouts, admission control, the program disk cache,
and the end-to-end mini-acceptance run.

Fast tests run at Grid(1, 1) in the main (single-device) pytest
process; the failure-isolation test runs on a real 2×1 mesh in a
subprocess (f64, so "neighbors solve bit-identically" is meaningful);
the full 4×2 traffic acceptance is ``slow``-marked (8 devices) and
covered nightly + by ``benchmarks/pselinv_bench.py``.
"""
import time

import numpy as np
import pytest
import scipy.sparse as sp

from conftest import run_sub

import jax.numpy as jnp

from repro.core import sparse
from repro.core.engine import (Grid, PlanOptions, PSelInvEngine,
                               bucket_size)
from repro.serve import (BatchWindow, ProgramDiskCache, RequestStatus,
                         SelInvServer, ServeConfig, ServeMetrics,
                         ServerOverloaded, SolveRequest,
                         StructureBatcher)


def _req(skey="s", submitted=None, deadline=None):
    r = SolveRequest(skey=skey, matrix=object(), deadline=deadline)
    if submitted is not None:
        r.submitted = submitted
    return r


# ---------------------------------------------------------------------
# units: bucket_size, metrics, batcher flush policy
# ---------------------------------------------------------------------

def test_bucket_size_pow2():
    assert [bucket_size(B) for B in (1, 2, 3, 4, 5, 8, 9, 13, 16, 17)] \
        == [1, 2, 4, 4, 8, 8, 16, 16, 16, 32]
    with pytest.raises(ValueError, match="batch size"):
        bucket_size(0)


def test_metrics_snapshot_shape():
    m = ServeMetrics()
    snap = m.snapshot()
    assert snap["submitted"] == snap["solved"] == 0
    assert snap["latency_p50_us"] is None
    assert snap["batch_occupancy_mean"] is None
    m.inc("submitted", 3)
    m.observe_latency(1e-3)
    m.observe_latency(3e-3)
    m.observe_batch(13, 16)
    m.set_queue_depth(7)
    m.set_queue_depth(2)
    snap = m.snapshot()
    assert snap["submitted"] == 3 and snap["batches"] == 1
    assert 1e3 <= snap["latency_p50_us"] <= 3e3
    assert snap["batch_occupancy_mean"] == pytest.approx(13 / 16)
    assert snap["batch_size_hist"] == {13: 1}
    assert snap["batch_bucket_hist"] == {16: 1}
    assert snap["queue_depth"] == 2 and snap["queue_depth_max"] == 7


def test_batcher_max_batch_flushes_immediately():
    b = StructureBatcher(BatchWindow(max_batch=4, max_wait_ms=1e6))
    now = time.monotonic()
    for _ in range(9):
        b.add(_req("s", submitted=now))
    batches, expired = b.pop_ready(now)
    # two full chunks flush now; the remainder waits out its window
    assert [len(x) for x in batches] == [4, 4] and not expired
    assert b.pending() == 1


def test_batcher_max_wait_flushes_partial():
    b = StructureBatcher(BatchWindow(max_batch=16, max_wait_ms=5.0))
    now = time.monotonic()
    b.add(_req("s", submitted=now))
    b.add(_req("t", submitted=now - 0.010))     # window already expired
    batches, _ = b.pop_ready(now)
    assert [len(x) for x in batches] == [1]
    assert batches[0][0].skey == "t"
    assert b.next_due(now) == pytest.approx(now + 0.005, abs=1e-6)
    batches, _ = b.pop_ready(now + 0.006)
    assert [len(x) for x in batches] == [1]
    assert b.pending() == 0


def test_batcher_pressure_flushes_fullest_queue():
    b = StructureBatcher(BatchWindow(max_batch=16, max_wait_ms=1e6,
                                     pressure=8))
    now = time.monotonic()
    for _ in range(7):
        b.add(_req("big", submitted=now))
    for _ in range(3):
        b.add(_req("small", submitted=now))
    batches, _ = b.pop_ready(now)
    # total backlog 10 > 8: the fullest queue flushes first, and that
    # alone brings the backlog under the bound
    assert [len(x) for x in batches] == [7]
    assert batches[0][0].skey == "big"
    assert b.pending() == 3


def test_batcher_expires_overdue_requests():
    b = StructureBatcher(BatchWindow(max_batch=4, max_wait_ms=1e6))
    now = time.monotonic()
    b.add(_req("s", submitted=now, deadline=now - 1.0))
    b.add(_req("s", submitted=now, deadline=now + 60.0))
    batches, expired = b.pop_ready(now, force=True)
    assert len(expired) == 1 and expired[0].deadline < now
    assert [len(x) for x in batches] == [1]


def test_request_future_semantics():
    r = _req()
    assert not r.done()
    with pytest.raises(TimeoutError, match="still queued"):
        r.result(timeout=0.01)
    r._finish(RequestStatus.SOLVED, result=42)
    assert r.done() and r.result() == 42
    r._finish(RequestStatus.FAILED, error=RuntimeError("late"))
    assert r.status is RequestStatus.SOLVED      # first completion wins


# ---------------------------------------------------------------------
# server end-to-end at Grid(1, 1), main process
# ---------------------------------------------------------------------

#: in-process tests run f32 (the main pytest process has no x64; the
#: f64 ≤1e-12 identity is asserted by the subprocess tests below and by
#: the bench harness) — batched-vs-unbatched f32 agreement bound
_F32_TOL = 1e-5


@pytest.fixture
def g11_server():
    PSelInvEngine.clear_cache()
    srv = SelInvServer(ServeConfig(
        b=8, grid=Grid(1, 1), dtype=jnp.float32,
        window=BatchWindow(max_batch=4, max_wait_ms=1.0)))
    yield srv
    srv.stop()


def test_server_coalesces_and_matches_unbatched(g11_server):
    """Same-structure requests coalesce into one batch whose per-request
    results match the engine's own unbatched solves (f64); a second
    structure lands in its own batch."""
    srv = g11_server
    A = sparse.laplacian_2d(12, 8)
    B = sparse.laplacian_2d(16, 8)
    I_A = sp.identity(A.shape[0])
    reqs = [srv.submit(A + c * I_A) for c in (0.0, 0.5, 1.0)]
    reqs.append(srv.submit(B))
    assert srv.pump(force=True) == 2             # one batch per structure
    eng = srv.engine_for(A)
    for c, r in zip((0.0, 0.5, 1.0), reqs[:3]):
        assert r.status is RequestStatus.SOLVED
        ref = np.asarray(eng.solve(A + c * I_A, dtype=jnp.float32))
        assert abs(np.asarray(r.result()) - ref).max() <= _F32_TOL
    assert reqs[3].status is RequestStatus.SOLVED
    st = srv.stats()
    assert st["solved"] == 4 and st["batches"] == 2
    assert len(st["structures"]) == 2
    assert st["batch_size_hist"] == {1: 1, 3: 1}


def test_server_bucket_padding_shares_programs(g11_server):
    """A batch of 3 rides the B=4 program: the engine traces once for
    the bucket, and a later exact-4 batch adds no trace."""
    srv = g11_server
    A = sparse.laplacian_2d(12, 8)
    I_A = sp.identity(A.shape[0])
    for c in (0.1, 0.2, 0.3):
        srv.submit(A + c * I_A)
    srv.pump(force=True)
    eng = srv.engine_for(A)
    assert eng.trace_count == 1
    st = srv.stats()
    skey = next(iter(st["structures"]))
    assert st["structures"][skey]["buckets_used"] == [4]
    assert st["batch_bucket_hist"] == {4: 1}
    for c in (0.4, 0.5, 0.6, 0.7):               # exact bucket, no pad
        srv.submit(A + c * I_A)
    srv.pump(force=True)
    assert eng.trace_count == 1                  # same compiled program


def test_server_admission_rejects_beyond_max_queue():
    PSelInvEngine.clear_cache()
    srv = SelInvServer(ServeConfig(
        b=8, grid=Grid(1, 1), max_queue=2,
        window=BatchWindow(max_batch=16, max_wait_ms=1e6)))
    A = sparse.laplacian_2d(12, 8)
    ok = [srv.submit(A) for _ in range(2)]
    rej = srv.submit(A)
    assert rej.status is RequestStatus.REJECTED
    with pytest.raises(ServerOverloaded, match="queue at capacity"):
        rej.result()
    assert srv.stats()["rejected"] == 1
    srv.pump(force=True)                         # admitted ones solve
    assert all(r.status is RequestStatus.SOLVED for r in ok)


def test_server_timeout_while_queued():
    PSelInvEngine.clear_cache()
    srv = SelInvServer(ServeConfig(
        b=8, grid=Grid(1, 1),
        window=BatchWindow(max_batch=16, max_wait_ms=1e6)))
    A = sparse.laplacian_2d(12, 8)
    r = srv.submit(A, timeout_ms=1.0)
    time.sleep(0.01)
    srv.pump()                   # no force: the deadline, not the
    assert r.status is RequestStatus.TIMED_OUT   # window, fired
    with pytest.raises(TimeoutError, match="missed its deadline"):
        r.result()
    assert srv.stats()["timed_out"] == 1


def test_server_background_worker_thread():
    """The background worker drives windows by itself: submits complete
    without any pump() from the caller."""
    PSelInvEngine.clear_cache()
    cfg = ServeConfig(b=8, grid=Grid(1, 1), dtype=jnp.float32,
                      window=BatchWindow(max_batch=4, max_wait_ms=1.0))
    A = sparse.laplacian_2d(12, 8)
    I_A = sp.identity(A.shape[0])
    with SelInvServer(cfg) as srv:
        reqs = [srv.submit(A + c * I_A) for c in (0.0, 0.5, 1.0, 2.0)]
        outs = [np.asarray(r.result(timeout=60)) for r in reqs]
        assert all(r.status is RequestStatus.SOLVED for r in reqs)
        eng = srv.engine_for(A)
        for c, o in zip((0.0, 0.5, 1.0, 2.0), outs):
            ref = np.asarray(eng.solve(A + c * I_A, dtype=jnp.float32))
            assert abs(o - ref).max() <= _F32_TOL
        assert srv.stats()["batches"] >= 1


def test_progcache_roundtrip(tmp_path):
    """The on-disk AOT cache: a miss compiles + persists, a fresh cache
    instance loads the serialized executable from disk, and both
    executables produce the engine's own batched result bit-for-bit —
    without touching trace_count."""
    PSelInvEngine.clear_cache()
    from repro.core.engine import stack_values
    A = sparse.laplacian_2d(12, 8)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                options=PlanOptions())
    v = eng.prepare_values(A)
    vb = stack_values([v, v])
    ref = np.asarray(eng.solve(vb, dtype=jnp.float32))
    t0 = eng.trace_count

    cache = ProgramDiskCache(str(tmp_path))
    comp = cache.get(eng, 2, jnp.float32)
    out = np.asarray(comp(jnp.asarray(vb.Lh, jnp.float32),
                          jnp.asarray(vb.Dinv, jnp.float32)))
    assert abs(out - ref).max() == 0.0
    assert cache.stats() == {"hits": 0, "misses": 1, "stores": 1,
                             "load_errors": 0, "entries": 1}
    assert cache.get(eng, 2, jnp.float32) is comp     # memory hit

    cache2 = ProgramDiskCache(str(tmp_path))          # "restart"
    comp2 = cache2.get(eng, 2, jnp.float32)
    out2 = np.asarray(comp2(jnp.asarray(vb.Lh, jnp.float32),
                            jnp.asarray(vb.Dinv, jnp.float32)))
    assert abs(out2 - ref).max() == 0.0
    assert cache2.stats()["hits"] == 1                # disk hit
    assert cache2.stats()["misses"] == 0
    assert eng.trace_count == t0                      # AOT is uncounted
    # a different bucket/dtype is its own entry
    assert cache.cache_key(eng, 2, jnp.float32) != \
        cache.cache_key(eng, 4, jnp.float32)


def test_server_through_progcache(tmp_path):
    """A server configured with the program cache serves through the
    persisted AOT executables and still matches unbatched solves."""
    PSelInvEngine.clear_cache()
    srv = SelInvServer(ServeConfig(
        b=8, grid=Grid(1, 1), dtype=jnp.float32,
        window=BatchWindow(max_batch=4, max_wait_ms=1.0),
        prog_cache=ProgramDiskCache(str(tmp_path))))
    A = sparse.laplacian_2d(12, 8)
    I_A = sp.identity(A.shape[0])
    reqs = [srv.submit(A + c * I_A) for c in (0.0, 1.0, 2.0)]
    srv.pump(force=True)
    eng = srv.engine_for(A)
    for c, r in zip((0.0, 1.0, 2.0), reqs):
        assert r.status is RequestStatus.SOLVED
        ref = np.asarray(eng.solve(A + c * I_A, dtype=jnp.float32))
        assert abs(np.asarray(r.result()) - ref).max() <= _F32_TOL
    st = srv.stats()
    assert st["prog_cache"]["misses"] == 1
    assert st["prog_cache"]["stores"] == 1


_PACKED_SERVE = """
import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp
from repro.core import sparse
from repro.core.engine import Grid, PSelInvEngine
from repro.serve import (BatchWindow, RequestStatus, SelInvServer,
                         ServeConfig)

A = sparse.laplacian_2d(9, 8)
I = sp.identity(A.shape[0])
mats = [A + c * I for c in (0.25, 1.0, 3.0)]
srv = SelInvServer(ServeConfig(
    b=8, grid=Grid({pr}, {pc}), dtype=jnp.float32,
    window=BatchWindow(max_batch=4, max_wait_ms=1.0)))
eng = srv.engine_for(A)
st0 = srv.stats()
reqs = [srv.submit(M) for M in mats]
srv.pump(force=True)
assert all(r.status is RequestStatus.SOLVED for r in reqs)
st = srv.stats()
assert st["h2d_packed_batches"] - st0["h2d_packed_batches"] == 1
assert st["h2d_dense_batches"] == st0["h2d_dense_batches"]
# the same batch forced through dense SolveValues: the same bits
dense = np.asarray(eng.solve(eng.prepare_values_many(mats),
                             dtype=jnp.float32, bucket=True))
for i, r in enumerate(reqs):
    got = np.asarray(r.result())
    assert np.array_equal(got.view(np.uint32), dense[i].view(np.uint32))
print("OK")
"""


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_served_packed_batch_is_bitwise_the_dense_one(grid):
    """A served all-matrix batch ships its factors packed, and its
    answers are bitwise those of the same batch solved through dense
    ``SolveValues`` — the sweep is given the same inputs either way."""
    pr, pc = grid
    assert "OK" in run_sub(_PACKED_SERVE.format(pr=pr, pc=pc),
                           ndev=pr * pc)


def test_mixed_batch_of_values_and_matrices_is_served(g11_server):
    """Pre-factorized values and matrices in one batch take the dense
    path and are served correctly: each answer is bitwise the one the
    same position gets in an all-matrix batch of the same matrices."""
    srv = g11_server
    A = sparse.laplacian_2d(12, 8)
    I_A = sp.identity(A.shape[0])
    mats = [A + c * I_A for c in (0.0, 0.5, 1.0)]
    eng = srv.engine_for(A)
    st0 = srv.stats()
    mixed = [srv.submit_values(eng, eng.prepare_values(mats[0])),
             srv.submit(mats[1]), srv.submit(mats[2])]
    assert srv.pump(force=True) == 1
    whole = [srv.submit(M) for M in mats]
    srv.pump(force=True)
    st = srv.stats()
    assert st["h2d_dense_batches"] - st0["h2d_dense_batches"] == 1
    assert st["h2d_packed_batches"] - st0["h2d_packed_batches"] == 1
    for m, w in zip(mixed, whole, strict=True):
        assert m.status is w.status is RequestStatus.SOLVED
        got, want = np.asarray(m.result()), np.asarray(w.result())
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    ref = np.asarray(eng.solve(mats[0], dtype=jnp.float32))
    assert abs(np.asarray(mixed[0].result()) - ref).max() <= _F32_TOL


# ---------------------------------------------------------------------
# failure isolation on a real 2x1 mesh (subprocess, f64)
# ---------------------------------------------------------------------

def test_failure_isolation_bad_request_fails_alone():
    """A request whose sparsity pattern escapes its claimed structure
    (submitted as pre-checked values would dodge admission — here it
    sneaks in by pattern-fingerprint collision simulation: same
    fingerprint path, corrupted matrix swapped onto the request) fails
    ALONE: its batch neighbors solve bit-identically to their unbatched
    solves and the server keeps serving the next window."""
    run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PSelInvEngine
        from repro.serve import (BatchWindow, RequestStatus,
                                 SelInvServer, ServeConfig, ServeError)

        PSelInvEngine.clear_cache()
        srv = SelInvServer(ServeConfig(
            b=8, grid=Grid(2, 1), dtype=jnp.float64,
            window=BatchWindow(max_batch=4, max_wait_ms=1.0)))
        A = sparse.laplacian_2d(12, 8)
        I = sp.identity(A.shape[0])
        good = [srv.submit(A + c * I) for c in (0.5, 1.5)]
        bad = srv.submit(A + 1.0 * I)
        # corrupt the queued request's payload *after* admission: an
        # out-of-structure block the engine's tables cannot represent
        E = sp.lil_matrix(A)
        E[0, 95] = E[95, 0] = 1.0
        bad.matrix = sp.csr_matrix(E)

        srv.pump(force=True)
        assert bad.status is RequestStatus.FAILED, bad.status
        try:
            bad.result()
            raise AssertionError("bad request returned a result")
        except ServeError as e:
            assert "outside the analyzed block" in str(e), e
        # neighbors solved, bit-identical to their unbatched solves
        eng = srv.engine_for(A)
        for c, r in zip((0.5, 1.5), good):
            assert r.status is RequestStatus.SOLVED, r.status
            ref = np.asarray(eng.solve(A + c * I, dtype=jnp.float64))
            assert abs(np.asarray(r.result()) - ref).max() <= 1e-12
        # ...and the server survives for the next window
        nxt = srv.submit(A + 3.0 * I)
        srv.pump(force=True)
        assert nxt.status is RequestStatus.SOLVED, nxt.status
        st = srv.stats()
        assert st["failed"] == 1 and st["solved"] == 3, st
        print("OK")
    """, ndev=2, x64=True)


# ---------------------------------------------------------------------
# the full acceptance harness on the 4x2 mesh (slow, 8 devices)
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_serve_traffic_acceptance_4x2():
    """The full harness on the 4×2 mesh with Poisson arrivals:
    compile conformance and ≤1e-12 identity are asserted strictly
    inside run_traffic; the throughput bar here is a sanity floor
    (coalescing must win) rather than the bench's ≥5× — with 8
    simulated devices plus Poisson sleeps sharing the host, the 4×2
    ratio swings run to run.  The asserted ≥5× lives in
    ``benchmarks/pselinv_bench.py --serve-bench`` on Grid(1, 1)."""
    out = run_sub("""
        import jax.numpy as jnp
        from repro.core.engine import Grid
        from repro.serve.batcher import BatchWindow
        from repro.serve.traffic import run_traffic

        res = run_traffic(n_requests=100, n_structures=2,
                          rate_hz=4000.0, seed=0, b=8, grid=Grid(4, 2),
                          window=BatchWindow(), dtype=jnp.float64,
                          check_identity=True, tol=1e-12, reps=3)
        assert res["speedup"] >= 1.5, res["speedup"]
        print(f"OK speedup={res['speedup']:.2f} "
              f"occ={res['serve_batch_occupancy']:.2f}")
    """, ndev=8, x64=True)
    assert "OK" in out
