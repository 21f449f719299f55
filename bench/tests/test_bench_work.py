"""The work count and the matrix generator against brute force and the
program's own symbolic analysis."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import matrices  # noqa: E402
import work      # noqa: E402


def brute_force_struct(Q, b):
    """Row blocks below each block column after block elimination on a
    dense boolean block pattern: eliminating block K joins every pair of
    row blocks present below it."""
    nb = Q.shape[0] // b
    P = (Q.toarray() != 0).reshape(nb, b, nb, b).any(axis=(1, 3))
    for K in range(nb):
        rows = np.nonzero(P[K + 1:, K])[0] + K + 1
        P[np.ix_(rows, rows)] = True
    return [[I for I in range(K + 1, nb) if P[I, K]] for K in range(nb)]


@pytest.mark.parametrize("nx,ny,b", [(8, 8, 8), (16, 16, 8), (32, 8, 8),
                                     (16, 8, 4)])
def test_block_structure_matches_brute_force(nx, ny, b):
    Q = matrices.Lattice(nx, ny).precision(7.0)
    st = work.block_structure(Q, b)
    assert st == brute_force_struct(Q, b)
    s1 = sum(len(c) for c in st)
    s2 = sum(len(c) ** 2 for c in st)
    assert work.useful_flops(st, b) == 2 * b ** 3 * (s1 + s2)
    assert work.needed_bytes(st, b) == (3 * s1 + 2 * len(st)) * b * b * 4


def test_useful_work_counts_block_products():
    # one block column with C = [1, 2]: A⁻¹(C,C)·L̂(C,K) is 4 block
    # products, the diagonal term 2; nothing below the last two columns
    st = [[1, 2], [2], []]
    b = 2
    assert work.useful_flops(st, b) == 2 * b ** 3 * ((4 + 2) + (1 + 1))
    # L̂ blocks 3, D⁻¹ blocks 3, A⁻¹ written 2·3 + 3
    assert work.needed_bytes(st, b, itemsize=8) == (3 + 3 + 9) * b * b * 8


@pytest.mark.parametrize("matrix,s1,s2", [
    ("program_laplacian_2d", 527, 2385),   # the 5-point program matrix
    ("spde_alpha2", 815, 5583)])           # the configurations' matrix
def test_full_size_counts(matrix, s1, s2):
    if matrix == "program_laplacian_2d":
        from repro.core import sparse
        Q = sparse.laplacian_2d(128, 128)
    else:
        Q = matrices.Lattice(128, 128).precision(7.0)
    st = work.block_structure(Q, 128)
    assert len(st) == 128
    assert sum(len(c) for c in st) == s1
    assert sum(len(c) ** 2 for c in st) == s2
    assert work.useful_flops(st, 128) == pytest.approx(
        2 * 128 ** 3 * (s2 + s1))


@pytest.mark.parametrize("nx,ny,b", [(16, 16, 8), (32, 128, 128)])
def test_structure_matches_the_programs(nx, ny, b):
    from repro.core.pselinv_dist import analyze_structure
    Q = matrices.Lattice(nx, ny).precision(6.0)
    bs, _ = analyze_structure(Q, b, 1, 1)
    assert work.block_structure(Q, b) == [
        [int(i) for i in s] for s in bs.struct]


def test_ranges_come_from_the_seed():
    a = matrices.ranges(2 ** 33 + 7, 5, 5.0, 10.0)
    assert np.array_equal(a, matrices.ranges(2 ** 33 + 7, 5, 5.0, 10.0))
    assert not np.array_equal(a, matrices.ranges(2 ** 33 + 8, 5, 5.0,
                                                  10.0))
    assert a.min() >= 5.0 and a.max() <= 10.0


def test_lattice_precision_is_the_papers_alpha2_stencil():
    # interior row: a^2 + 4 at the centre, -2a on the axes, 2 on the
    # diagonals, 1 two cells out on the axes, a = kappa^2 + 4
    nx = ny = 9
    lat = matrices.Lattice(nx, ny)
    rho = 6.0
    a = 8.0 / rho ** 2 + 4.0
    Q = lat.precision(rho).toarray()
    perm = matrices.nested_dissection(nx, ny)
    inv = np.argsort(perm)                 # old index -> new index
    at = lambda x, y: inv[x * ny + y]      # noqa: E731
    c = at(4, 4)
    assert Q[c, c] == pytest.approx(a * a + 4)
    for (x, y), v in {(5, 4): -2 * a, (4, 3): -2 * a, (5, 5): 2.0,
                      (3, 5): 2.0, (6, 4): 1.0, (4, 2): 1.0,
                      (6, 5): 0.0}.items():
        assert Q[c, at(x, y)] == pytest.approx(v)
    assert np.allclose(Q, Q.T)
    assert sorted(perm.tolist()) == list(range(nx * ny))
