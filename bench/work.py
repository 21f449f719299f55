"""The work selected inversion needs, counted from the matrix alone.

The block structure is worked out here from the sparsity pattern by
block-level symbolic elimination, not read from the program, so the
count stays the same whatever implements the sweep. With supernodes of
uniform width ``b`` (the partition the program requires), the row
blocks ``C_K`` below block column ``K`` of the filled factor are the
pattern's own lower blocks of column ``K`` plus, for every child ``J``
of ``K`` in the block elimination tree, ``C_J`` without ``K``.
"""
from __future__ import annotations

from typing import List

import numpy as np
import scipy.sparse as sp

__all__ = ["block_structure", "useful_flops", "needed_bytes"]


def block_structure(A, b: int) -> List[List[int]]:
    """``C_K`` for every block column ``K``: the sorted row blocks
    ``I > K`` present in the filled block factor of ``A`` (a matrix with
    a symmetric pattern and ``n`` a multiple of ``b``)."""
    A = sp.coo_matrix(A)
    n = A.shape[0]
    if n % b:
        raise ValueError(f"n={n} is not a multiple of b={b}")
    nb = n // b
    rows, cols = A.row // b, A.col // b
    hi, lo = np.maximum(rows, cols), np.minimum(rows, cols)
    off = hi != lo
    struct = [set() for _ in range(nb)]
    for i, k in set(zip(hi[off].tolist(), lo[off].tolist())):
        struct[k].add(i)
    for K in range(nb):
        C = sorted(struct[K])
        if len(C) > 1:
            struct[C[0]].update(C[1:])    # C[0] is K's parent
    return [sorted(s) for s in struct]


def useful_flops(struct: List[List[int]], b: int) -> float:
    """Multiply-adds (×2) the SelInv recurrence needs: for each block
    column, A⁻¹(C,C)·L̂(C,K) (|C|² block products) and the diagonal
    term L̂(C,K)ᵀ·A⁻¹(C,K) (|C| products), each 2b³."""
    s1 = sum(len(c) for c in struct)
    s2 = sum(len(c) ** 2 for c in struct)
    return 2.0 * b ** 3 * (s2 + s1)


def needed_bytes(struct: List[List[int]], b: int,
                 itemsize: int = 4) -> float:
    """Bytes the recurrence has to move at least once: the
    struct-present L̂ blocks (Σ|C|) and the D⁻¹ blocks (nb) read, and the
    selected A⁻¹ blocks written (both triangles and the diagonal,
    2Σ|C| + nb), ``b²·itemsize`` bytes each."""
    s1 = sum(len(c) for c in struct)
    nb = len(struct)
    return float((s1 + nb + 2 * s1 + nb) * b * b * itemsize)
