"""Comm-schedule + simulator tests, incl. the paper's Table-1 claims."""
import numpy as np
import pytest

from repro.core import sparse
from repro.core.schedule import Grid2D, pselinv_events
from repro.core.simulator import (NetworkModel, _msgs_vector, simulate,
                                  volume_stats, volumes, volumes_fast)
from repro.core.symbolic import symbolic_factorize_elements
from repro.core.trees import HYBRID_FLAT_MAX, TreeKind


@pytest.fixture(scope="module")
def small_case():
    G, sizes = sparse.fem3d_like_structure(8, 8, 8, 3)
    bs = symbolic_factorize_elements(G, sizes, max_supernode=12)
    return bs, Grid2D(8, 8)


def test_events_well_formed(small_case):
    bs, grid = small_case
    events, tasks = pselinv_events(bs, grid)
    assert events and tasks
    for ev in events:
        assert ev.root in ev.participants
        assert len(set(ev.participants)) == len(ev.participants)
        assert ev.nbytes > 0
        for r in ev.participants:
            assert 0 <= r < grid.size


@pytest.mark.parametrize("kind", list(TreeKind))
def test_fast_volume_path_matches_slow(small_case, kind):
    bs, grid = small_case
    out, _ = volumes(bs, grid, kind)
    fast = volumes_fast(bs, grid, kind)
    np.testing.assert_allclose(out["col-bcast"], fast["col-bcast"])
    np.testing.assert_allclose(out["row-reduce"], fast["row-reduce"])


def test_volume_conservation(small_case):
    """Total bytes sent == total bytes received per event kind."""
    bs, grid = small_case
    out, inc = volumes(bs, grid, TreeKind.SHIFTED)
    for kind in out:
        assert out[kind].sum() == pytest.approx(inc[kind].sum())


def test_total_volume_scheme_invariant(small_case):
    """Tree shape redistributes but does not change total traffic for
    broadcasts with identical participant sets per event... (flat and
    binary carry identical per-event message counts = p-1)."""
    bs, grid = small_case
    a = volumes_fast(bs, grid, TreeKind.FLAT)["col-bcast"].sum()
    b = volumes_fast(bs, grid, TreeKind.BINARY)["col-bcast"].sum()
    c = volumes_fast(bs, grid, TreeKind.SHIFTED)["col-bcast"].sum()
    assert a == pytest.approx(b)
    assert a == pytest.approx(c)


def test_paper_table1_directional_claims():
    """Binary raises max/σ vs flat under concurrency; shifted lowers σ
    and max and raises min (paper Table 1)."""
    G, sizes = sparse.fem3d_like_structure(16, 16, 16, 3)
    bs = symbolic_factorize_elements(G, sizes, max_supernode=12)
    grid = Grid2D(32, 32)
    stats = {k: volume_stats(volumes_fast(bs, grid, k)["col-bcast"])
             for k in (TreeKind.FLAT, TreeKind.BINARY, TreeKind.SHIFTED)}
    flat, binry, shift = (stats[TreeKind.FLAT], stats[TreeKind.BINARY],
                          stats[TreeKind.SHIFTED])
    assert binry["max"] > flat["max"]
    assert binry["std"] > flat["std"]
    assert shift["std"] < flat["std"]
    assert shift["max"] < flat["max"]
    assert shift["min"] > flat["min"]


def test_simulation_shifted_beats_flat_at_scale(small_case):
    bs, _ = small_case
    grid = Grid2D(32, 32)
    t_flat = simulate(bs, grid, TreeKind.FLAT, NetworkModel()).total_time
    t_shift = simulate(bs, grid, TreeKind.SHIFTED,
                       NetworkModel()).total_time
    assert t_shift < t_flat


def test_simulation_deterministic(small_case):
    bs, grid = small_case
    m = NetworkModel(jitter_sigma=0.3, placement_seed=7)
    t1 = simulate(bs, grid, TreeKind.SHIFTED, m).total_time
    t2 = simulate(bs, grid, TreeKind.SHIFTED, m).total_time
    assert t1 == t2


def test_msgs_vector_resolves_hybrid():
    """HYBRID handed to the fast-path tree accounting resolves to the
    concrete kind ``build_tree`` would pick at that participant count —
    flat at/below HYBRID_FLAT_MAX participants, shifted (with the
    caller's tag-derived rotation, NOT a shift-0 tree) above it."""
    small = tuple(range(1, HYBRID_FLAT_MAX))        # 24 participants
    np.testing.assert_array_equal(
        _msgs_vector(TreeKind.HYBRID, 0, small, 5, HYBRID_FLAT_MAX),
        _msgs_vector(TreeKind.FLAT, 0, small, 0, HYBRID_FLAT_MAX))
    big = tuple(range(1, HYBRID_FLAT_MAX + 1))      # 25 participants
    n = HYBRID_FLAT_MAX + 1
    np.testing.assert_array_equal(
        _msgs_vector(TreeKind.HYBRID, 0, big, 5, n),
        _msgs_vector(TreeKind.SHIFTED, 0, big, 5, n))
    # a shift-0 tree would be a different schedule — the old dead ternary
    # silently produced exactly that
    assert not np.array_equal(
        _msgs_vector(TreeKind.HYBRID, 0, big, 5, n),
        _msgs_vector(TreeKind.SHIFTED, 0, big, 0, n))
