"""The plain reference against a dense inverse, and the control (the
reference at ``Precision.HIGH``) failing the configurations' limit."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import matrices   # noqa: E402
import reference  # noqa: E402
import work       # noqa: E402


def _limit(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)["limits"]["max_rel_err"]


def _dense_grid(Q, b):
    inv = np.linalg.inv(Q.toarray())
    nb = Q.shape[0] // b
    return inv.reshape(nb, b, nb, b).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("rho", [5.0, 10.0])
def test_reference_matches_dense_inverse(rho):
    Q = matrices.Lattice(16, 16).precision(rho)
    st = work.block_structure(Q, 8)
    ref = reference.selected_inverse(Q, 8, st)
    assert reference.max_rel_err(_dense_grid(Q, 8), ref, st) < 1e-12


def test_bf16x3_product_error():
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal((2, 64, 64)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    rel = lambda x: np.abs(x - exact).max() / np.abs(exact).max()  # noqa
    # three bf16 passes lose the lo·lo term: far above f32, far below bf16
    assert 1e-7 < rel(reference.mm_bf16x3(a, b)) < 1e-4
    assert rel(a @ b) < 1e-6
    assert rel(reference._bf16(a) @ reference._bf16(b)) > 1e-3


@pytest.mark.parametrize("config", ["gmrf2d-128x128-b128",
                                    "gmrf2d-32x128-b128"])
def test_control_fails_the_limit(config):
    # the configurations' own limit, at a size a test run holds: the
    # control fails it, the same recurrence in plain float32 (what an
    # f32 matmul at HIGHEST computes) stays under it
    with open(os.path.join(REPO, "bench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    lattice = matrices.Lattice(16, 16)
    for seed in (1, 2 ** 33 + 1, 7):
        (rho,) = matrices.ranges(seed, 1, *cfg["range_cells"])
        Q = lattice.precision(rho)
        st = work.block_structure(Q, 8)
        ref = reference.selected_inverse(Q, 8, st)
        ctl = reference.control(Q, 8, st, cfg["precision"], 1, 1)
        assert reference.max_rel_err(
            reference.unshard(ctl, len(st), 8, 1, 1), ref, st) \
            > _limit(config)
        f32 = reference.selected_inverse(Q, 8, st, dtype=np.float32)
        assert reference.max_rel_err(
            reference.full_grid(f32, len(st), 8), ref, st) \
            < _limit(config)


def test_comparison_covers_every_selected_block():
    Q = matrices.Lattice(16, 16).precision(7.0)
    st = work.block_structure(Q, 8)
    ref = reference.selected_inverse(Q, 8, st)
    got = _dense_grid(Q, 8).copy()
    assert reference.max_rel_err(got, ref, st) < 1e-12
    K = 3
    for I in [K] + st[K]:                       # lower and diagonal
        bad = got.copy()
        bad[I, K] *= 1.001
        assert reference.max_rel_err(bad, ref, st) > 1e-5
    J = next(J for J in range(len(st)) if st[J])
    bad = got.copy()
    bad[J, st[J][0]] *= 1.001                    # an upper block
    assert reference.max_rel_err(bad, ref, st) > 1e-5
    bad = got.copy()
    bad[K, K, 0, 0] = np.nan
    assert reference.max_rel_err(bad, ref, st) == float("inf")


def test_unshard_matches_the_programs_layout():
    from repro.core.pselinv_dist import _shard_blocks
    nb, b, pr, pc = 8, 2, 2, 2
    G = np.arange(nb * nb * b * b, dtype=np.float64).reshape(nb, nb, b, b)
    shards = _shard_blocks(G, nb, b, pr, pc)
    assert np.array_equal(reference.unshard(shards, nb, b, pr, pc), G)
    assert np.array_equal(reference.shard(G, pr, pc), shards)
