"""The program's spans from inside: ``TRACER`` spans on the profiler's
host plane, the disabled path, the compile listener, the served batch's
sub-spans, the sweep's ``sweep.*`` scopes in the compiled HLO, and the
benchmark readers of the new spans."""
import glob
import os
import re
import sys
import time
import tracemalloc

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from conftest import run_sub

from repro.core import sparse
from repro.core.engine import Grid, PlanOptions, PSelInvEngine
from repro.obs import compiles
from repro.obs.trace import TRACER, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "bench"))

import run as bench_run  # noqa: E402


@pytest.fixture
def traced():
    """The process tracer, cleared and enabled for one test."""
    TRACER.clear()
    TRACER.enable()
    try:
        yield TRACER
    finally:
        TRACER.disable()
        TRACER.clear()


# ---------------------------------------------------------------------------
# the tracer on the profiler's clock
# ---------------------------------------------------------------------------

def test_span_lands_on_profiler_host_plane_with_attrs(tmp_path):
    """Under the bench's profile a span shows up natively on the host
    plane, with its attributes as stats, and where the anchor puts the
    ring buffer's copy (within 1 ms)."""
    from jax.profiler import ProfileData

    prof = bench_run.Profile(True, str(tmp_path / "trace"))
    prof.start()
    try:
        with TRACER.span("probe.outer", B=4, tag="x") as sp_:
            time.sleep(0.002)
            sp_.set(late=7)
            with TRACER.span("probe.inner"):
                time.sleep(0.001)
    finally:
        prof.stop()
    ring = {s.name: s for s in TRACER.spans()}
    TRACER.clear()
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("anchor", "probe.outer", "probe.inner"):
                        host[e.name] = e
    offset = host["anchor"].start_ns - prof.anchor_ns
    for name in ("probe.outer", "probe.inner"):
        want = ring[name].t0_us * 1e3 + offset
        assert abs(host[name].start_ns - want) < 1e6, name
        assert abs(host[name].duration_ns - ring[name].dur_us * 1e3) < 1e6
    stats = dict(host["probe.outer"].stats)
    assert stats["B"] == 4 and stats["tag"] == "x" and stats["late"] == 7
    assert ring["probe.outer"].attrs == {"B": 4, "tag": "x", "late": 7}


def test_disabled_tracer_makes_no_annotation_allocation_or_record(
        monkeypatch):
    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            pass

    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    t = Tracer()
    for _ in range(10):
        with t.span("a", x=1) as s:
            s.set(y=2)
    t.record("r", 0.5)
    assert made == [] and t.spans() == []
    # no per-span allocation: memory does not grow over many spans
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        for _ in range(20000):
            with t.span("a", x=1) as s:
                s.set(y=2)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak - base < 4096
    # the compile listener records nothing while TRACER is off
    compiles.install()
    TRACER.clear()
    assert not TRACER.enabled
    jax.jit(lambda v: v * 3 + 1)(jnp.ones((5, 11))).block_until_ready()
    assert TRACER.spans() == []
    # enabled, the same tracer opens one annotation per span
    t.enable()
    with t.span("b"):
        pass
    assert made == ["b"]


def test_new_shape_records_compile_spans(traced):
    compiles.install()
    f = jax.jit(lambda v: jnp.sin(v) * 2)
    f(jnp.ones((7, 13))).block_until_ready()
    comp = [s for s in traced.spans() if s.name == "jax.compile"]
    assert {s.attrs["stage"] for s in comp} >= {"trace", "lower",
                                                 "compile"}
    assert all(s.dur_us >= 0 for s in comp)
    traced.clear()
    f(jnp.ones((7, 13))).block_until_ready()     # warm: nothing new
    assert not [s for s in traced.spans() if s.name == "jax.compile"]


# ---------------------------------------------------------------------------
# the served batch and the engine's value path
# ---------------------------------------------------------------------------

def test_serve_batch_children_cover_it_and_carry_rids(traced):
    from repro.serve import SelInvServer, ServeConfig

    A = sparse.laplacian_2d(16, 16)
    srv = SelInvServer(ServeConfig(b=8, grid=Grid(1, 1)))
    mats = [A + s * sp.identity(A.shape[0]) for s in (0.0, 0.5, 1.0)]
    for M in mats:                         # compile the batch of 3 first
        srv.submit(M)
    srv.drain()
    traced.clear()
    reqs = [srv.submit(M) for M in mats]
    srv.drain()
    assert all(r.done() for r in reqs)
    spans = traced.spans()
    (batch,) = [s for s in spans if s.name == "serve.batch"]
    assert batch.attrs["rids"] == [r.rid for r in reqs]
    kids = {s.name: s for s in spans if s.parent_id == batch.span_id}
    assert set(kids) == {"serve.pattern_check", "serve.prepare",
                         "serve.sweep", "serve.d2h"}
    assert sum(s.dur_us for s in kids.values()) >= 0.9 * batch.dur_us
    by_id = {s.span_id: s for s in spans}

    def parent(name):
        (s,) = [x for x in spans if x.name == name]
        return by_id[s.parent_id].name

    for step in ("prep.check", "prep.densify", "prep.factor",
                 "prep.layout"):
        assert parent(step) == "engine.prepare_values_many"
        (s,) = [x for x in spans if x.name == step]
        assert s.attrs["B"] == 3
    (dens,) = [s for s in spans if s.name == "prep.densify"]
    assert dens.attrs["nnz"] == sum(sp.csr_matrix(M).nnz for M in mats)
    assert dens.attrs["summed"] == 0
    (h2d,) = [s for s in spans if s.name == "engine.h2d"]
    assert parent("engine.h2d") == "engine.solve"
    assert h2d.attrs["B"] == 3
    # bucket 4 of f32 values: what went to the device, padding included
    nb = srv.engine_for(A).nb
    assert h2d.attrs["bytes"] == 2 * 4 * nb * nb * 8 * 8 * 4
    assert not [s for s in spans if s.name == "jax.compile"]


_PREP = ("engine.prepare_values", "engine.prepare_values_many")


@pytest.mark.parametrize("via", ["direct", "served_batch_of_one"])
def test_one_matrix_prep_is_one_span_the_bench_reads_once(traced, via):
    """``prep_s_per_matrix.served`` sums ``engine.prepare_values`` and
    ``engine.prepare_values_many``. One matrix, prepared directly or
    served alone, yields exactly one of them — the direct call the
    first, the served batch of one the second — which covers
    ``prep.check``, ``prep.densify``, ``prep.factor`` and
    ``prep.layout``, each with ``B=1``; neither name nests in the other,
    so the reader counts the matrix once, for that span's time."""
    from repro.serve import SelInvServer, ServeConfig

    A = sparse.laplacian_2d(16, 16)
    if via == "direct":
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1))
        traced.clear()
        eng.prepare_values(A)
        want = "engine.prepare_values"
    else:
        srv = SelInvServer(ServeConfig(b=8, grid=Grid(1, 1)))
        srv.submit(A)                      # compile the batch of 1 first
        srv.drain()
        traced.clear()
        r = srv.submit(A + 0.5 * sp.identity(A.shape[0]))
        srv.drain()
        assert r.done() and r.result(0).shape[0] == 1
        want = "engine.prepare_values_many"
    spans = traced.spans()
    prep = [s for s in spans if s.name in _PREP]
    assert [s.name for s in prep] == [want]
    (top,) = prep
    by_id = {s.span_id: s for s in spans}
    up = top.parent_id
    while up is not None:
        assert by_id[up].name not in _PREP
        up = by_id[up].parent_id
    for step in ("prep.check", "prep.densify", "prep.factor",
                 "prep.layout"):
        (s,) = [x for x in spans if x.name == step]
        assert s.parent_id == top.span_id and s.attrs["B"] == 1
        assert top.t0_us <= s.t0_us and s.t1_us <= top.t1_us
    run = {"spans": [(s.name, s.dur_us / 1e6, s.attrs) for s in spans]}
    assert bench_run.read_metric(REPO, "prep_s_per_matrix.served", run) \
        == pytest.approx(top.dur_us / 1e6)


def test_prep_densify_counts_the_members_it_summed(traced):
    """A batch member stored with duplicate entries takes the summing
    path, and ``prep.densify`` says so: ``summed`` counts it and not the
    member whose indices are merely unsorted; ``nnz`` counts every
    stored entry, duplicates included."""
    A = sparse.laplacian_2d(16, 16)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1))
    dup = sp.csr_matrix((np.repeat(A.data / 2, 2),     # each entry as
                         np.repeat(A.indices, 2),       # two halves
                         2 * A.indptr), shape=A.shape)
    rows = np.repeat(np.arange(A.shape[0]), np.diff(A.indptr))
    desc = np.lexsort((-A.indices, rows))      # each row's columns reversed
    rev = sp.csr_matrix((A.data[desc], A.indices[desc], A.indptr),
                        shape=A.shape)
    assert not dup.has_canonical_format and not rev.has_sorted_indices
    traced.clear()
    eng.prepare_values_many([A, dup, rev])
    (dens,) = [s for s in traced.spans() if s.name == "prep.densify"]
    assert dens.attrs["B"] == 3
    assert dens.attrs["summed"] == 1
    assert dens.attrs["nnz"] == 2 * A.nnz + dup.nnz


class _NoClock:
    def __getattr__(self, name):
        raise AssertionError(f"read the clock: time.{name}")


def test_value_path_reads_no_clock_and_adds_no_wait_when_disabled(
        monkeypatch):
    from repro.core import engine as engine_mod
    from repro.obs import trace as trace_mod
    from repro.serve import SelInvServer, ServeConfig

    A = sparse.laplacian_2d(8, 8)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1))
    eng.solve(eng.prepare_values(A)).block_until_ready()    # compile
    srv = SelInvServer(ServeConfig(b=8, grid=Grid(1, 1)))
    srv.submit(A)
    srv.drain()
    assert not TRACER.enabled
    monkeypatch.setattr(engine_mod, "time", _NoClock())
    monkeypatch.setattr(trace_mod, "time", _NoClock())

    def no_wait(x):
        raise AssertionError("waited for the device while not tracing")

    monkeypatch.setattr(jax, "block_until_ready", no_wait)
    recorded = []
    monkeypatch.setattr(TRACER, "record",
                        lambda *a, **kw: recorded.append(a))
    vals = eng.prepare_values(A)
    many = eng.prepare_values_many([A, A])
    eng.solve(vals).block_until_ready()
    assert many.Lh.shape[0] == 2
    r = srv.submit(A + sp.identity(A.shape[0]))
    srv.drain()
    assert r.result(0).shape[0] == 1
    # a fresh shape compiles; the listener hands TRACER nothing
    jax.jit(lambda v: v - 2)(jnp.ones((3, 17))).block_until_ready()
    assert recorded == []


# ---------------------------------------------------------------------------
# sweep.* scopes in the compiled program
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled_text(eng):
    sd = jax.ShapeDtypeStruct((1, eng.nb, eng.nb, eng.b, eng.b),
                              jnp.float32)
    fn = jax.jit(eng._shard_mapped_sweep(False, counted=False))
    return fn.trace(sd, sd).lower().compile().as_text()


@pytest.mark.parametrize("stream", [False, True],
                         ids=["overlapped", "stream"])
def test_sweep_scopes_in_compiled_hlo(stream):
    A = sparse.laplacian_2d(8, 8)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                options=PlanOptions(stream=stream))
    assert eng.nb == 8
    txt = _compiled_text(eng)
    names = set(_OP_NAME.findall(txt))
    for step in ("gemm", "write", "scomp", "diagw", "lanes", "init",
                 "finish"):
        assert any(f"sweep.{step}" in n for n in names), step
    # the level GEMM (a dot on the CPU, the Pallas custom-call on TPU)
    gemm = [m.group(1) for line in txt.splitlines()
            if re.search(r" (dot|custom-call)\(", line)
            for m in [_OP_NAME.search(line)] if m]
    assert any("sweep.gemm" in n and n.endswith("dot_general")
               for n in gemm), gemm
    # static levels nest under the kind in the overlapped executor only
    levels = any(re.search(r"sweep\.gemm/level\d+/", n) for n in names)
    assert levels is not stream


def test_sweep_permute_scope_on_every_ppermute_2x2():
    run_sub("""
        import re
        import jax, jax.numpy as jnp
        from repro.core import sparse
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine
        A = sparse.laplacian_2d(16, 8)
        for opts in (PlanOptions(), PlanOptions(stream=True)):
            eng = PSelInvEngine.analyze(A, b=8, grid=Grid(2, 2),
                                        options=opts)
            sd = jax.ShapeDtypeStruct(
                (4, eng.nb // 2, eng.nb // 2, 8, 8), jnp.float32)
            fn = jax.jit(eng._shard_mapped_sweep(False, counted=False))
            txt = fn.trace(sd, sd).lower().compile().as_text()
            perms = [l for l in txt.splitlines()
                     if re.search(r" collective-permute(-start)?\\(", l)]
            assert perms, opts
            bad = [l for l in perms if "sweep.permute" not in l]
            assert not bad, (opts, bad[:2])
        print("OK")
    """, ndev=4)


# ---------------------------------------------------------------------------
# the restricted collectives, counted once per session
# ---------------------------------------------------------------------------

_COMM = ("rounds", "wire_bytes", "recv_bytes_max", "recv_bytes_mean")

_COMM_2X2 = """
    import jax.numpy as jnp
    from repro.core import engine as engine_mod, hlo_verify, simulator
    from repro.core import sparse
    from repro.core.engine import (Grid, PlanOptions, PSelInvEngine,
                                   stack_values)
    from repro.obs.registry import REGISTRY
    from repro.obs.trace import TRACER
    A = sparse.laplacian_2d(16, 8)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(2, 2),
                                options=PlanOptions({opts}))
    c = dict(eng.comm_counters())
    assert c["wire_bytes"] == simulator.executed_wire_bytes(eng) > 0
    assert c["recv_bytes_max"] >= c["recv_bytes_mean"] > 0
    count = eng.compile_stats()["ppermute_count"]
    if {stream}:
        # one loop body replays the rounds; its permutes are the slots
        slots = len(hlo_verify.expected_permutes(eng.program))
        assert count == slots < c["rounds"], (count, slots, c)
    else:
        assert count == c["rounds"], (count, c)
    st = eng.stats()
    assert st["ppermute_rounds"] == c["rounds"]
    for k in ("wire_bytes", "recv_bytes_max", "recv_bytes_mean"):
        assert st[k] == c[k] == REGISTRY.get("selinv_engine_" + k).value

    def recount(*a, **kw):
        raise AssertionError("counted again")

    simulator.volumes_from_plan = simulator.executed_wire_bytes = recount
    engine_mod.ppermute_round_count = recount
    vals = eng.prepare_values(A)
    eng.solve(vals, dtype=jnp.float64).block_until_ready()
    assert TRACER.spans() == []
    TRACER.enable()
    eng.solve(vals, dtype=jnp.float64).block_until_ready()
    eng.solve(vals, dtype=jnp.float32).block_until_ready()
    eng.solve(stack_values([vals] * 3), dtype=jnp.float32,
              bucket=True).block_until_ready()
    f64, f32, batch = [s.attrs for s in TRACER.spans()
                       if s.name == "engine.solve"]
    assert {{k: f64[k] for k in c}} == c
    # priced at what each solve ships: 4-byte elements, 4 bucketed lanes
    for attrs, scale in ((f32, 0.5), (batch, 4 * 0.5)):
        assert attrs["rounds"] == c["rounds"]
        for k in ("wire_bytes", "recv_bytes_max", "recv_bytes_mean"):
            assert attrs[k] == c[k] * scale, (k, attrs, c)
    print("OK")
"""


@pytest.mark.parametrize("opts", ["", "stream=True"],
                         ids=["overlapped", "stream"])
def test_comm_counters_on_solve_span_2x2(opts):
    """At grid 2x2: ``wire_bytes`` is the simulator's executed wire,
    ``rounds`` the compiled permutes of the overlapped executor (the
    stream's loop body holds one per comm slot), the most a device
    receives is at least the mean, the gauges carry them, and a solve
    counts nothing again: off it records nothing, on it stamps the
    counts made at ``analyze``."""
    out = run_sub(_COMM_2X2.format(opts=opts, stream="stream" in opts),
                  ndev=4, x64=True)
    assert "OK" in out


def test_comm_counters_at_grid_1x1_are_zero(traced):
    A = sparse.laplacian_2d(8, 8)
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1))
    eng.solve(eng.prepare_values(A)).block_until_ready()
    (solve,) = [s for s in traced.spans() if s.name == "engine.solve"]
    assert {k: solve.attrs[k] for k in _COMM} == dict.fromkeys(_COMM, 0)


# ---------------------------------------------------------------------------
# the benchmark's readers of the new spans
# ---------------------------------------------------------------------------

_SPANS = [
    ("engine.prepare_values_many", 9.0, {"B": 4}),
    ("prep.factor", 4.0, {"B": 4}),
    ("prep.factor", 1.0, {"B": 1}),
    ("engine.h2d", 0.5, {"B": 4, "bytes": 10}),
    ("engine.h2d", 0.25, {"B": 1}),
    ("serve.sweep", 0.3, {"B": 4, "bucket": 4}),
    ("serve.d2h", 0.2, {"B": 4}),
    ("serve.d2h", 0.05, {"B": 1}),
    ("jax.compile", 0.01, {"stage": "trace", "fun": "f"}),
    ("jax.compile", 0.02, {"stage": "lower", "fun": "jit(f)"}),
    ("jax.compile", 0.03, {"stage": "compile", "fun": "jit(f)"}),
]


@pytest.mark.parametrize("name,full,empty", [
    ("prep_factor_s_per_matrix.served", 5.0 / 5, None),
    ("h2d_s_per_matrix.served", 0.75 / 5, None),
    ("d2h_s_per_matrix.served", 0.25 / 5, None),
    ("window_compiles.served", 1, None),
])
def test_span_readers_on_synthetic_runs(name, full, empty):
    assert bench_run.read_metric(REPO, name, {"spans": _SPANS}) \
        == pytest.approx(full)
    # a program without these spans, as the parent's, reads nothing
    old = [("engine.prepare_values_many", 9.0, {"B": 4})]
    assert bench_run.read_metric(REPO, name, {"spans": old}) is empty
    assert bench_run.read_metric(REPO, name, {}) is empty


def test_window_compiles_reads_zero_in_a_steady_window():
    steady = [s for s in _SPANS if s[0] != "jax.compile"]
    assert bench_run.read_metric(REPO, "window_compiles.served",
                                 {"spans": steady}) == 0
