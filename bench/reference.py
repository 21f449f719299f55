"""Plain reference for the selected inverse, and the comparison.

The reference is a textbook blocked algorithm on the host, independent
of the code under test: block Cholesky ``Q = L·Lᵀ`` over the filled
block structure (``work.block_structure``), then the SelInv recurrence
from the last block column to the first,

    L̂(C,K)  = L(C,K)·L(K,K)⁻¹,        D(K)⁻¹ = L(K,K)⁻ᵀ·L(K,K)⁻¹,
    A⁻¹(C,K) = −A⁻¹(C,C)·L̂(C,K),      A⁻¹(K,K) = D(K)⁻¹ − L̂(C,K)ᵀ·A⁻¹(C,K).

The factors ``L̂`` and ``D⁻¹`` are made in float64, as the program's
host prep makes them. The reference runs the recurrence in float64 with
``np.matmul``. The control, the reference put in the program's place at
the precision below the configuration's, casts the factors to float32
as the program does and runs the recurrence with ``mm_bf16x3``: each
product as a TPU computes an f32 matmul at ``Precision.HIGH`` (three
bf16 passes, f32 accumulation). Products of bf16 values are exact in
f32, so the emulation differs from the chip only in the order of
accumulation.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp

__all__ = ["factor", "selected_inverse", "control", "mm_bf16x3",
           "unshard", "max_rel_err"]

Blocks = Dict[Tuple[int, int], np.ndarray]


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept in
    float32."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def mm_bf16x3(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a @ b`` in float32 as three bf16 passes: hi·hi + hi·lo + lo·hi."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (ah @ bl + al @ bh) + ah @ bh


#: the configuration's precision -> the control's dtype and products:
#: an f32 matmul at ``Precision.HIGH`` below one at ``HIGHEST``
CONTROLS = {"f32_highest": (np.float32, mm_bf16x3)}


def factor(Q, b: int, struct: List[List[int]]
           ) -> Tuple[Dict[int, np.ndarray], Dict[int, np.ndarray]]:
    """``(L̂, D⁻¹)`` in float64, the program's host prep: block Cholesky
    over the filled structure, then per block column ``K`` the panel
    ``L̂(C,K)`` stacked ``(|C|·b, b)`` and ``D(K)⁻¹``."""
    Q = sp.csr_matrix(Q)
    nb = len(struct)
    L: Blocks = {}
    for K in range(nb):
        for I in [K] + struct[K]:
            L[I, K] = Q[I * b:(I + 1) * b, K * b:(K + 1) * b].toarray()

    def panel(K: int) -> np.ndarray:      # L(C,K) stacked, (|C|·b, b)
        return np.concatenate([L[I, K] for I in struct[K]])

    Lhat: Dict[int, np.ndarray] = {}
    Dinv: Dict[int, np.ndarray] = {}
    eye = np.eye(b)
    for K in range(nb):
        C = struct[K]
        LKK = np.linalg.cholesky(L[K, K])
        Linv = sla.solve_triangular(LKK, eye, lower=True)
        Dinv[K] = Linv.T @ Linv
        if not C:
            continue
        # L(C,K) = Q(C,K)·L(K,K)⁻ᵀ, then the Schur update of the clique
        P = sla.solve_triangular(LKK, panel(K).T, lower=True).T
        S = P @ P.T
        for i, I in enumerate(C):
            L[I, K] = P[i * b:(i + 1) * b]
            for j, J in enumerate(C[:i + 1]):
                upd = S[i * b:(i + 1) * b, j * b:(j + 1) * b]
                L[I, J] = L[I, J] - upd if (I, J) in L else -upd
        Lhat[K] = panel(K) @ Linv
    return Lhat, Dinv


def selected_inverse(Q, b: int, struct: List[List[int]], *,
                     dtype=np.float64,
                     mm: Callable = np.matmul) -> Blocks:
    """Lower-triangle blocks of Q⁻¹ on the selected pattern: ``(K, K)``
    and ``(I, K)`` for ``I`` in ``struct[K]``. The factors come from
    :func:`factor` in float64 and are cast to ``dtype``; the recurrence
    runs in ``dtype`` with every block product through ``mm``."""
    Lhat, Dinv = factor(Q, b, struct)
    inv: Blocks = {}
    for K in reversed(range(len(struct))):
        C = struct[K]
        D = Dinv[K].astype(dtype)
        if not C:
            inv[K, K] = D
            continue
        Lh = Lhat[K].astype(dtype)                       # L̂(C,K)
        Acc = np.block([[inv[I, J] if I >= J else inv[J, I].T
                         for J in C] for I in C])        # A⁻¹(C,C)
        X = -mm(Acc, Lh)                                 # A⁻¹(C,K)
        for i, I in enumerate(C):
            inv[I, K] = X[i * b:(i + 1) * b]
        inv[K, K] = D - mm(Lh.T, X)
    return inv


def control(Q, b: int, struct: List[List[int]], precision: str,
            pr: int, pc: int) -> np.ndarray:
    """The control in the program's place: the reference at the
    precision just below ``precision``, as the program's output shards
    on a ``pr × pc`` grid."""
    dtype, mm = CONTROLS[precision]
    return shard(full_grid(selected_inverse(Q, b, struct, dtype=dtype,
                                            mm=mm), len(struct), b),
                 pr, pc)


def full_grid(blocks: Blocks, nb: int, b: int) -> np.ndarray:
    """Lower-triangle blocks to a symmetric ``(nb, nb, b, b)`` grid."""
    G = np.zeros((nb, nb, b, b), next(iter(blocks.values())).dtype)
    for (i, j), v in blocks.items():
        G[i, j] = v
        G[j, i] = v.T
    return G


def shard(grid: np.ndarray, pr: int, pc: int) -> np.ndarray:
    """The inverse of :func:`unshard`."""
    nb, _, b, _ = grid.shape
    nbr, nbc = nb // pr, nb // pc
    return (grid.reshape(nbr, pr, nbc, pc, b, b)
            .transpose(1, 3, 0, 2, 4, 5).reshape(pr * pc, nbr, nbc, b, b))


def unshard(out: np.ndarray, nb: int, b: int, pr: int,
            pc: int) -> np.ndarray:
    """The program's output shards ``(pr·pc, nb/pr, nb/pc, b, b)``, block
    ``(I, J)`` on device ``(I mod pr)·pc + J mod pc``, back to the
    ``(nb, nb, b, b)`` block grid."""
    nbr, nbc = nb // pr, nb // pc
    return (np.asarray(out).reshape(pr, pc, nbr, nbc, b, b)
            .transpose(2, 0, 3, 1, 4, 5).reshape(nb, nb, b, b))


def max_rel_err(got: np.ndarray, ref: Blocks,
                struct: List[List[int]]) -> float:
    """Largest relative Frobenius error, over block columns ``K``, of
    every selected block of that column: the diagonal, the lower blocks
    ``(I, K)`` for ``I`` in ``struct[K]`` and the upper blocks ``(J, K)``
    for ``K`` in ``struct[J]``. ``got`` is an ``(nb, nb, b, b)`` grid."""
    nb = len(struct)
    upper: List[List[int]] = [[] for _ in range(nb)]
    for J in range(nb):
        for K in struct[J]:
            upper[K].append(J)
    worst = 0.0
    for K in range(nb):
        rows = [K] + struct[K] + upper[K]
        want = [ref[I, K] for I in [K] + struct[K]] + \
               [ref[K, J].T for J in upper[K]]
        diff = sum(float(np.sum((got[I, K].astype(np.float64) - w) ** 2))
                   for I, w in zip(rows, want))
        norm = sum(float(np.sum(np.asarray(w, np.float64) ** 2))
                   for w in want)
        err = (diff / norm) ** 0.5
        if not np.isfinite(err):          # NaN must not read as small
            return float("inf")
        worst = max(worst, err)
    return worst
