"""The trace reduction on synthetic intervals and on a small trace
recorded on a TPU v5e (three solves of an nb=8, b=128 sweep on one
chip, with the harness's ``solve``/``wait`` annotations)."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import reduce  # noqa: E402

TRACE = os.path.join(HERE, "data", "sweep_1x1_nb8.xplane.pb")


def test_union_and_subtract():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert reduce.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert reduce.subtract([(0, 3), (5, 8)], []) == [(0, 3), (5, 8)]
    assert reduce.subtract([(0, 3)], [(0, 3)]) == []


def test_opcode():
    name = ("%copy-start.22 = (s32[8]{0:T(128)S(1)}, s32[8]{0:T(128)}, "
            "u32[]{:S(2)}) copy-start(s32[8]{0:T(128)} %constant.540)")
    assert reduce.opcode(name) == ("copy-start.22", "copy-start")
    name = ("%collective-permute-done.3 = f32[4,128]{1,0} "
            "collective-permute-done(f32[4,128]{1,0} %cp)")
    assert reduce.opcode(name) == ("collective-permute-done.3",
                                   "collective-permute-done")
    assert reduce.opcode("jit_sweep(42)") == ("jit_sweep(42)",
                                              "jit_sweep(42)")


def _op(name, op, s, e):
    return (f"%{name} = f32[2]{{0}} {op}(f32[2]{{0}} %x)", s, e)


def test_summarize_synthetic():
    tr = reduce.Trace(
        ops={0: [_op("fusion.1", "fusion", 0, 40),
                 _op("cp-start", "collective-permute-start", 40, 45),
                 _op("fusion.2", "fusion", 45, 60),
                 _op("cp-done", "collective-permute-done", 60, 80),
                 _op("fusion.1", "fusion", 150, 190)]},
        async_ops={0: [_op("cp-start", "collective-permute-start",
                           40, 80)]})
    spans = [("solve", 0, 100), ("wait", 90, 140), ("solve", 140, 200)]
    s = reduce.summarize(tr, (0, 200), spans)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx(120e-9)
    assert s["collective_s"] == pytest.approx(40e-9)
    # the transfer overlaps fusion.2 (45-60); 40-45 and 60-80 are exposed
    assert s["exposed_collective_s"] == pytest.approx(25e-9)
    assert s["op_s"]["fusion.1 fusion"] == pytest.approx(80e-9)
    # gaps 80-150 and 190-200: 80-90 under solve only, 90-100 under both
    # (the shorter, wait, names it), 100-140 wait, 140-150 and 190-200 solve
    assert s["gap_s"]["wait"] == pytest.approx(50e-9)
    assert s["gap_s"]["solve"] == pytest.approx(30e-9)
    assert reduce.top(s["op_s"], 1) == [["fusion.1 fusion",
                                         pytest.approx(80e-9)]]


def test_gap_naming_ties_and_none():
    tr = reduce.Trace(ops={0: [_op("f", "fusion", 0, 10),
                               _op("f", "fusion", 20, 30)]})
    # gap 10-20: both spans cover it, the shorter one names it
    s = reduce.summarize(tr, (0, 40), [("outer", 0, 40), ("inner", 5, 25)])
    assert s["gap_s"] == {"inner": pytest.approx(10e-9),
                          "outer": pytest.approx(10e-9)}
    s = reduce.summarize(tr, (0, 40), [("solve", 0, 5)])
    assert s["gap_s"] == {"none": pytest.approx(20e-9)}
    # a gap partly covered: 10-15 by the span, 15-20 by none
    s = reduce.summarize(tr, (0, 30), [("solve", 12, 15)])
    assert s["gap_s"] == {"solve": pytest.approx(3e-9),
                          "none": pytest.approx(7e-9)}


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over event boundaries (a second way)."""
    pts = sorted([(max(s, lo), 1) for _, s, e in events if e > lo and s < hi]
                 + [(min(e, hi), -1) for _, s, e in events
                    if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(TRACE, host_names=("solve", "wait"))


def test_recorded_trace(recorded):
    tr = recorded
    assert list(tr.ops) == [0] and len(tr.ops[0]) == 501
    assert sorted(n for n, _, _ in tr.host) == ["solve"] * 3 + ["wait"] * 3
    lo = min(s for _, s, _ in tr.host)
    hi = max(e for _, _, e in tr.host)
    s = reduce.summarize(tr, (lo, hi), tr.host)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert s["busy_s"] == pytest.approx(
        _busy_by_sweep(tr.ops[0], lo, hi) / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    # the Pallas level GEMM is the costliest op; no collectives on 1 chip
    assert reduce.top(s["op_s"], 1)[0][0].startswith("block_gemm_pallas")
    assert s["collective_s"] == 0.0
    # every idle nanosecond is named, and only by the harness's spans
    assert set(s["gap_s"]) <= {"solve", "wait", "none"}
    assert sum(s["gap_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert sum(s["op_s"].values()) >= s["busy_s"] * (1 - 1e-9)
