"""The configurations' matrices, made from the configuration and a seed.

A GMRF precision on an ``nx × ny`` lattice from the SPDE model of
Lindgren, Rue & Lindström (2011) at α = 2 (Matérn smoothness ν = 1,
R-INLA's default), in the paper's regular-lattice form with unit spacing
and lumped mass: ``Q = (κ²·I + G)²`` with ``G`` the 5-point lattice graph
Laplacian (degree minus adjacency, a free boundary). In the interior
this is the paper's α = 2 stencil with ``a = κ² + 4``. ``κ = √8 / ρ``
for a practical range ``ρ`` in lattice cells, drawn from the seed. The
marginal scale τ multiplies ``Q`` and leaves every relative error as it
is, so τ = 1.

``Q`` couples nodes two cells apart, so the ordering is geometric nested
dissection with separators two lines wide. Kept here, apart from the
program, so the yardstick does not move with it.
"""
from __future__ import annotations

from typing import List

import numpy as np
import scipy.sparse as sp

__all__ = ["nested_dissection", "Lattice", "ranges"]


def nested_dissection(nx: int, ny: int, sep: int = 2,
                      leaf: int = 4) -> np.ndarray:
    """Geometric nested dissection of an ``nx × ny`` lattice: split the
    longer axis by ``sep`` middle lines, order both halves recursively
    and the separator last. ``perm[new] = old``."""
    idx = np.arange(nx * ny).reshape(nx, ny)

    def rec(block: np.ndarray) -> List[int]:
        axis = int(np.argmax(block.shape))
        n = block.shape[axis]
        if n <= leaf:
            return block.reshape(-1).tolist()
        lo, hi = n // 2 - sep // 2, n // 2 - sep // 2 + sep
        return (rec(np.take(block, range(0, lo), axis=axis))
                + rec(np.take(block, range(hi, n), axis=axis))
                + np.take(block, range(lo, hi), axis=axis)
                .reshape(-1).tolist())

    return np.asarray(rec(idx), dtype=np.int64)


class Lattice:
    """The SPDE precision of one lattice, for any practical range; every
    range gives the same sparsity pattern."""

    def __init__(self, nx: int, ny: int):
        X, Y = np.meshgrid(np.arange(nx), np.arange(ny), indexing="ij")
        X, Y = X.ravel(), Y.ravel()
        ii, jj = [], []
        for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
            Xn, Yn = X + dx, Y + dy
            ok = (Xn >= 0) & (Xn < nx) & (Yn >= 0) & (Yn < ny)
            ii.append(X[ok] * ny + Y[ok])
            jj.append(Xn[ok] * ny + Yn[ok])
        i, j = np.concatenate(ii), np.concatenate(jj)
        n = nx * ny
        adj = sp.csr_matrix((np.ones(i.size), (i, j)), shape=(n, n))
        G = (sp.diags(np.asarray(adj.sum(axis=1)).ravel()) - adj).tocsr()
        perm = nested_dissection(nx, ny)
        self.G = G[perm][:, perm].tocsr()
        self.GG = (self.G @ self.G).tocsr()
        self.eye = sp.identity(n, format="csr")

    def precision(self, range_cells: float) -> sp.csr_matrix:
        """``(κ²·I + G)²``, nested-dissection ordered, with
        ``κ² = 8 / ρ²``."""
        k2 = 8.0 / float(range_cells) ** 2
        return (k2 * k2 * self.eye + 2.0 * k2 * self.G + self.GG).tocsr()


def ranges(seed: int, count: int, lo: float, hi: float) -> np.ndarray:
    """``count`` practical ranges uniform in ``[lo, hi]`` from ``seed``."""
    return np.random.default_rng(seed).uniform(lo, hi, count)
