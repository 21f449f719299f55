"""The readers of the restricted collectives on synthetic runs: the
trace summary ``reduce.summarize`` gives and the program's spans, and
each reader's None where the run has nothing for it (the parent's spans
carry no counts)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import run as bench_run  # noqa: E402

COUNTS = {"rounds": 486, "wire_bytes": 8e9, "recv_bytes_max": 2.4e9,
          "recv_bytes_mean": 2e9}
TRACE = {"devices": 4, "window_s": 21.0, "busy_s": 20.0,
         "collective_s": 2.0, "exposed_collective_s": 0.5, "op_s": {},
         "gap_s": {}}
RUN = {"trace": TRACE, "solves": 4, "chips": 4,
       "spans": [("engine.h2d", 0.01, {"B": 1})]
       + [("engine.solve", 5.0, dict(COUNTS, B=1))] * 4}


def read(name, run):
    return bench_run.read_metric(REPO, name, run)


def test_readers_on_a_traced_run():
    assert read("exposed_collective_share.sweep2x2", RUN) == \
        pytest.approx(100 * 0.5 / 20.0)
    # 8 GB over 4 chips a solve, in 2.0 s / 4 solves of permutes
    assert read("wire_gbps.sweep2x2", RUN) == pytest.approx(4.0)
    assert read("recv_imbalance.sweep2x2", RUN) == pytest.approx(1.2)


@pytest.mark.parametrize("name", ["wire_gbps.sweep2x2",
                                  "recv_imbalance.sweep2x2"])
def test_span_readers_none_without_counts(name):
    parent = dict(RUN, spans=[("engine.solve", 5.0, {"B": 1})] * 4)
    assert read(name, parent) is None
    assert read(name, {}) is None


@pytest.mark.parametrize("name,run", [
    ("exposed_collective_share.sweep2x2", {}),
    ("exposed_collective_share.sweep2x2",
     dict(RUN, trace=dict(TRACE, devices=0, busy_s=0.0))),
    ("wire_gbps.sweep2x2", dict(RUN, trace=None)),
    ("wire_gbps.sweep2x2", dict(RUN, trace=dict(TRACE, collective_s=0.0))),
    ("wire_gbps.sweep2x2", dict(RUN, solves=0)),
    # one chip: nothing is received, so no imbalance
    ("recv_imbalance.sweep2x2",
     dict(RUN, spans=[("engine.solve", 1.0,
                       dict.fromkeys(COUNTS, 0))])),
], ids=["no-trace", "no-busy", "wire-no-trace", "no-collective",
        "no-solves", "one-chip"])
def test_readers_none_with_nothing_to_read(name, run):
    assert read(name, run) is None
