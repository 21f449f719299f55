"""SweepScope observability tier: span tracer semantics, the unified
metrics registry, the Chrome-trace exporter schema, serve-metrics
backward compatibility, engine stat gauges, and the profile_rounds
measured-timeline conformance contract (subprocess, 8 devices)."""
import json

import numpy as np
import pytest

from conftest import run_sub

from repro.obs.registry import REGISTRY, MetricsRegistry
from repro.obs.trace import Tracer


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_attr_roundtrip():
    t = Tracer(enabled=True)
    with t.span("outer", a=1) as sp:
        sp.set(b="two")
        with t.span("inner", c=3.0):
            pass
    spans = t.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert outer.attrs == {"a": 1, "b": "two"}
    assert inner.attrs == {"c": 3.0}
    # timing sanity: inner nests inside outer on the same clock
    assert outer.t0_us <= inner.t0_us
    assert inner.t1_us <= outer.t1_us + 1.0
    assert outer.dur_us >= 0 and inner.dur_us >= 0


def test_span_records_exception_and_reraises():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    (s,) = t.spans()
    assert s.attrs["error"] == "ValueError"


def test_disabled_tracer_null_fast_path():
    t = Tracer(enabled=False)
    # the disabled path hands back one shared singleton — no per-call
    # allocation, nothing buffered, attrs silently dropped
    s1 = t.span("a", x=1)
    s2 = t.span("b")
    assert s1 is s2
    with s1 as sp:
        assert sp.set(y=2) is sp
    t.instant("marker")
    assert t.spans() == [] and len(t) == 0 and t.dropped == 0
    # flipping the switch restores real spans on the same tracer
    t.enable()
    with t.span("real"):
        pass
    assert [s.name for s in t.spans()] == ["real"]


def test_ring_buffer_bounded_with_drop_counter():
    t = Tracer(capacity=4, enabled=True)
    for i in range(6):
        with t.span(f"s{i}"):
            pass
    spans = t.spans()
    assert len(spans) == 4
    assert t.dropped == 2
    assert [s.name for s in spans] == ["s2", "s3", "s4", "s5"]  # oldest out
    t.clear()
    assert t.spans() == [] and t.dropped == 0


def test_tracer_thread_local_nesting():
    import threading
    t = Tracer(enabled=True)
    seen = {}

    def worker():
        with t.span("child-thread"):
            pass
        seen["done"] = True

    with t.span("main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    spans = {s.name: s for s in t.spans()}
    assert seen["done"]
    # the worker's span must NOT parent under main's open span
    assert spans["child-thread"].parent_id is None
    assert spans["child-thread"].tid != spans["main"].tid


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("c_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(5)
    g.inc(2)
    g.max(3)          # below current → no-op
    assert g.value == 7.0
    g.max(11)
    assert g.value == 11.0
    # idempotent registration returns the same object...
    assert r.counter("c_total") is c
    # ...and a kind/label mismatch is an error, not a silent replace
    with pytest.raises(ValueError):
        r.gauge("c_total")
    with pytest.raises(ValueError):
        r.counter("c_total", labelnames=("x",))


def test_registry_labeled_children():
    r = MetricsRegistry()
    c = r.counter("events_total", labelnames=("name",))
    c.labels("solved").inc()
    c.labels("solved").inc()
    c.labels(name="failed").inc()
    assert {k: v.value for k, v in dict(c.children()).items()} == {
        ("solved",): 2.0, ("failed",): 1.0}
    with pytest.raises(ValueError):   # plain inc on a labeled metric
        c.inc()
    with pytest.raises(ValueError):   # wrong label arity
        c.labels("a", "b")


def test_histogram_percentiles_match_numpy():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds")
    assert h.percentile(50) is None and h.mean is None
    rng = np.random.default_rng(0)
    xs = rng.lognormal(size=500)
    for x in xs:
        h.observe(x)
    assert h.count == 500
    assert h.sum == pytest.approx(xs.sum())
    assert h.mean == pytest.approx(xs.mean())
    assert float(h.percentile(95)) == pytest.approx(
        float(np.percentile(xs, 95)))
    p50, p99 = h.percentile((50, 99))
    assert float(p50) == pytest.approx(float(np.percentile(xs, 50)))
    s = h.summary()
    assert s["count"] == 500 and s["p99"] == pytest.approx(float(p99))


def test_histogram_reservoir_bounded_but_count_exact():
    r = MetricsRegistry()
    h = r.histogram("h", max_samples=10)
    for i in range(25):
        h.observe(float(i))
    assert h.count == 25
    assert h.sum == float(sum(range(25)))
    assert len(h.samples()) == 10        # keep-the-head policy


def test_registry_snapshot_and_prometheus_text():
    r = MetricsRegistry()
    r.counter("reqs_total", "requests", labelnames=("name",)) \
        .labels("ok").inc(3)
    r.gauge("depth", "queue depth").set(7)
    h = r.histogram("lat", "latency")
    h.observe(1.0)
    h.observe(3.0)
    snap = r.snapshot()
    assert snap["reqs_total"] == {"name=ok": 3.0}
    assert snap["depth"] == 7.0
    assert snap["lat"]["count"] == 2 and snap["lat"]["mean"] == 2.0
    json.dumps(snap)                      # JSON-able end to end
    text = r.prometheus_text()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{name="ok"} 3' in text
    assert "# TYPE lat summary" in text
    assert "lat_count 2" in text and "lat_sum 4" in text
    assert 'lat{quantile="0.5"} 2' in text
    assert "depth 7" in text


# ---------------------------------------------------------------------------
# serve metrics — thin wrappers over the registry, frozen snapshot shape
# ---------------------------------------------------------------------------

def test_serve_metrics_snapshot_backward_compatible():
    from repro.serve.metrics import COUNTERS, ServeMetrics
    m = ServeMetrics()
    snap0 = m.snapshot()
    for name in COUNTERS:
        assert snap0[name] == 0
    assert snap0["latency_p50_us"] is None
    assert snap0["latency_mean_us"] is None
    assert snap0["batch_occupancy_mean"] is None
    assert snap0["queue_depth"] == 0 and snap0["queue_depth_max"] == 0

    m.inc("submitted", 4)
    m.inc("solved", 3)
    m.inc("failed")
    for s in (1e-3, 2e-3, 3e-3, 10e-3):
        m.observe_latency(s)
    m.observe_batch(3, 4, cause="window")
    m.observe_batch(4, 4, cause="full")
    m.set_queue_depth(5)
    m.set_queue_depth(2)
    snap = m.snapshot()
    assert snap["submitted"] == 4 and snap["solved"] == 3
    assert snap["failed"] == 1 and snap["batches"] == 2
    lat = np.array([1e-3, 2e-3, 3e-3, 10e-3]) * 1e6
    assert snap["latency_p50_us"] == pytest.approx(
        float(np.percentile(lat, 50)))
    assert snap["latency_p95_us"] == pytest.approx(
        float(np.percentile(lat, 95)))
    assert snap["latency_mean_us"] == pytest.approx(float(lat.mean()))
    assert snap["batch_occupancy_mean"] == pytest.approx((0.75 + 1.0) / 2)
    assert snap["batch_size_hist"] == {3: 1, 4: 1}
    assert snap["batch_bucket_hist"] == {4: 2}
    assert snap["flush_causes"] == {"full": 1, "window": 1}
    assert snap["queue_depth"] == 2 and snap["queue_depth_max"] == 5
    # the serving tier is scrape-able through the registry surface
    text = m.registry.prometheus_text()
    assert 'selinv_serve_events_total{name="solved"} 3' in text
    assert "selinv_serve_latency_seconds_count 4" in text


def test_serve_metrics_registries_are_isolated():
    from repro.serve.metrics import ServeMetrics
    a, b = ServeMetrics(), ServeMetrics()
    a.inc("submitted")
    assert a.snapshot()["submitted"] == 1
    assert b.snapshot()["submitted"] == 0
    assert a.registry is not b.registry


# ---------------------------------------------------------------------------
# Chrome-trace exporter — golden schema
# ---------------------------------------------------------------------------

def _fake_profile():
    from repro.obs.rounds import RoundProfile, RoundSample
    samples = [
        RoundSample(index=0, rounds=(0,), wall_us=100.0, sim_us=10.0,
                    wire_bytes=512.0, lane_bytes=256.0, msgs=2,
                    compute_ops=0, pure_comm=True),
        RoundSample(index=1, rounds=(1, 2), wall_us=200.0, sim_us=30.0,
                    wire_bytes=1024.0, lane_bytes=768.0, msgs=3,
                    compute_ops=2, pure_comm=False),
    ]
    return RoundProfile(
        nrounds=3, nranks=2, b=8, chunk=2, samples=samples,
        init_us=50.0, final_us=25.0, final_sim_us=5.0,
        inbound_bytes=np.array([256.0, 768.0]),
        inbound_msgs=np.array([2, 3]),
        inbound_time_us=np.array([120.0, 180.0]),
        rank_bytes=np.array([[256.0, 0.0], [0.0, 768.0]]))


def test_chrome_trace_schema_golden():
    from repro.obs.export import chrome_trace
    from repro.serve.batcher import RequestStatus, SolveRequest
    t = Tracer(enabled=True)
    with t.span("engine.analyze", nb=4):
        with t.span("analyze.symbolic"):
            pass
    req = SolveRequest(skey="deadbeef" * 5)
    req.batched_at = req.submitted + 1e-3
    req.completed = req.submitted + 3e-3
    req.status = RequestStatus.SOLVED

    doc = chrome_trace(spans=t.spans(), profile=_fake_profile(),
                       requests=[req])
    doc = json.loads(json.dumps(doc, default=float))  # wire round-trip
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events, "empty trace"
    for ev in events:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["name"], str)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":                    # complete events
            assert ev["ts"] >= 0.0
            assert ev["dur"] >= 0.0
            assert ev["cat"] in ("span", "round", "request")
    # all three sources present, on distinct process lanes
    pids = {ev["pid"] for ev in events if ev["ph"] == "X"}
    assert pids == {1, 2, 3}
    names = {ev["name"] for ev in events}
    assert {"engine.analyze", "analyze.symbolic", "rounds 1-2",
            "queued", "batched"} <= names
    # nested span linkage survives export
    by_name = {ev["name"]: ev for ev in events if ev["ph"] == "X"}
    assert (by_name["analyze.symbolic"]["args"]["parent_id"]
            == by_name["engine.analyze"]["args"]["span_id"])
    # per-rank round lanes carry the inbound payload of that rank only
    rank_evs = [ev for ev in events
                if ev["ph"] == "X" and ev["pid"] == 2 and ev["tid"] > 0]
    assert {ev["args"]["inbound_bytes"] for ev in rank_evs} == {256.0, 768.0}


def test_write_trace_perfetto_loadable(tmp_path):
    from repro.obs.export import write_trace
    t = Tracer(enabled=True)
    with t.span("solo"):
        pass
    path = write_trace(str(tmp_path / "t.trace.json"), spans=t.spans())
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert any(ev["ph"] == "X" and ev["name"] == "solo"
               for ev in doc["traceEvents"])


# ---------------------------------------------------------------------------
# engine instrumentation (single device — Grid(1, 1))
# ---------------------------------------------------------------------------

def test_engine_stats_gauges_and_compile_guard():
    import scipy.sparse as sp

    import jax

    from repro.core import sparse
    from repro.core.engine import Grid, PlanOptions, PSelInvEngine

    A = sp.csr_matrix(sparse.laplacian_2d(4, 8))
    # distinctive coalesce_max: a fresh cache key, so the session is
    # guaranteed never-solved regardless of suite ordering
    eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                options=PlanOptions(coalesce_max=5))
    st = eng.stats()
    # the solve/prep walls are spans now, not gauges
    assert "last_solve_us" not in st and "prepare_us" not in st
    assert st["solve_calls"] == 0
    # stats(compile=True) on a never-compiled session must not blow up:
    # it device-checks then compiles the f32 single-matrix class
    st = eng.stats(compile=True)
    assert st["compile_ms"] > 0
    vals = eng.prepare_values(A)
    jax.block_until_ready(eng.solve(vals))
    st = eng.stats()
    assert st["solve_calls"] == 1
    assert "last_solve_us" not in st and "prepare_us" not in st
    # every numeric stat is published to the global scrape surface
    g = REGISTRY.get("selinv_engine_solve_calls")
    assert g is not None and g.value == st["solve_calls"]
    assert REGISTRY.get("selinv_engine_ppermute_rounds").value \
        == st["ppermute_rounds"]
    assert REGISTRY.get("selinv_engine_last_solve_us") is None
    assert REGISTRY.get("selinv_engine_prepare_us") is None


def test_engine_spans_cover_analyze_and_solve():
    import scipy.sparse as sp

    import jax

    from repro.core import sparse
    from repro.core.engine import Grid, PlanOptions, PSelInvEngine
    from repro.obs.trace import TRACER

    A = sp.csr_matrix(sparse.laplacian_2d(4, 8))
    TRACER.clear()
    TRACER.enable()
    try:
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(1, 1),
                                    options=PlanOptions(coalesce_max=7))
        vals = eng.prepare_values(A)
        jax.block_until_ready(eng.solve(vals))
    finally:
        TRACER.disable()
    names = [s.name for s in TRACER.spans()]
    for expected in ("engine.analyze", "analyze.symbolic", "plan.build",
                     "plan.schedule", "plan.verify",
                     "engine.prepare_values", "engine.solve"):
        assert expected in names, (expected, names)
    spans = {s.name: s for s in TRACER.spans()}
    # the pipeline sub-spans parent under engine.analyze
    top = spans["engine.analyze"]
    assert spans["analyze.symbolic"].parent_id == top.span_id
    assert top.attrs["cache"] == "miss"
    assert top.attrs["nb"] == eng.nb


# ---------------------------------------------------------------------------
# profile_rounds conformance — 8 devices, subprocess
# ---------------------------------------------------------------------------

def test_profile_rounds_conformance_8dev():
    out = run_sub("""
        import numpy as np
        import scipy.sparse as sp
        import jax
        from repro.core import sparse
        from repro.core.engine import Grid, PSelInvEngine
        from repro.core.simulator import (executed_wire_bytes,
                                          simulate_schedule)
        from repro.core.schedule import BYTES_PER_ELT

        A = sp.csr_matrix(sparse.laplacian_2d(16, 8))
        eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2))
        vals = eng.prepare_values(A)
        ref = np.asarray(jax.block_until_ready(eng.solve(vals)))

        prof = eng.profile_rounds(vals, reps=1)
        ov = eng.program.overlap_plan

        # (1) the measured timeline covers the plan's rounds exactly
        assert prof.nrounds == len(ov.rounds), (prof.nrounds,
                                                len(ov.rounds))
        assert len(prof.samples) == len(ov.rounds)
        covered = [r for s in prof.samples for r in s.rounds]
        assert covered == list(range(len(ov.rounds)))

        # (2) per-round wire bytes re-derive the executed wire total
        per_round = [len(r.perm) * r.width * eng.b * eng.b
                     * BYTES_PER_ELT for r in ov.rounds]
        for s, w in zip(prof.samples, per_round):
            assert s.wire_bytes == w, (s.index, s.wire_bytes, w)
        assert prof.wire_bytes() == executed_wire_bytes(eng.program)

        # (3) the simulated join sums to the simulator's total
        sim = simulate_schedule(eng.program).total_time * 1e6
        assert abs(prof.sim_us - sim) / sim < 1e-9, (prof.sim_us, sim)

        # (4) the replay IS the sweep: bit-identical A^-1
        assert np.array_equal(np.asarray(prof.ainv), ref)

        # (5) measured walls are real (fenced, nonzero)
        assert all(s.wall_us > 0 for s in prof.samples)
        assert prof.init_us > 0 and prof.final_us > 0

        # (6) inbound joins match the plan's edge tables
        edges = [e for r in ov.rounds for e in r.edges]
        assert prof.inbound_bytes.sum() == sum(e[4] for e in edges)
        assert prof.inbound_msgs.sum() == len(edges)
        sk = prof.skew()
        assert sk["skew_ratio"] >= 1.0
        assert isinstance(sk["exceeds_static_warn"], bool)
        alpha, beta = prof.fit_alpha_beta()
        assert alpha >= 0 and beta >= 0

        # (7) chunked replay: same coverage, same wire accounting
        prof4 = eng.profile_rounds(vals, chunk=4, reps=1)
        covered4 = [r for s in prof4.samples for r in s.rounds]
        assert covered4 == list(range(len(ov.rounds)))
        assert prof4.wire_bytes() == executed_wire_bytes(eng.program)
        assert np.array_equal(np.asarray(prof4.ainv), ref)
        print("conformance ok:", prof.nrounds, "rounds,",
              int(prof.wire_bytes()), "wire bytes")
    """)
    assert "conformance ok: 28 rounds, 177152 wire bytes" in out
