"""jit'd public wrappers for the Pallas kernels.

On the CPU backend kernels run in ``interpret=True`` mode — the kernel
body executes in Python/XLA for correctness validation. On a TPU the
same calls compile to Mosaic."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .block_gemm import block_gemm_pallas
from .flash_attention import flash_attention_pallas
from .rmsnorm import rmsnorm_pallas
from .trsm import trsm_pallas

__all__ = ["block_gemm", "block_gemm_acc", "flash_attention", "rmsnorm",
           "trsm", "use_interpret", "pselinv_level_gemm",
           "pselinv_round_gemm"]


def use_interpret() -> bool:
    """Interpret mode exactly when the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def block_gemm(a, b):
    return block_gemm_pallas(a, b, interpret=use_interpret())


def block_gemm_acc(acc, a, b, alpha=-1.0):
    """acc + alpha·(a@b) — the Schur-update form used by supernodal LU."""
    return acc + block_gemm_pallas(a, b, alpha=alpha,
                                   interpret=use_interpret())


def pselinv_level_gemm(Ainv, Uh_m):
    """The sweep's masked block-GEMM for one elimination-tree level:
    ``partial[k, i] = Σ_j Ainv[i, j] @ Uh_m[k, j]ᵀ`` — all of a level's
    supernodes in one 2-D tiled matmul (MXU-shaped on TPU via the Pallas
    kernel; plain XLA dot as the CPU reference path).

    Ainv: (nbr, nbc, b, b) local A⁻¹ block grid; Uh_m: (nk, nbc, b, b)
    struct-masked Û stack. Returns (nk, nbr, b, b) partial products."""
    nbr, nbc, b, _ = Ainv.shape
    nk = Uh_m.shape[0]
    a2 = Ainv.transpose(0, 2, 1, 3).reshape(nbr * b, nbc * b)
    b2 = Uh_m.transpose(1, 3, 0, 2).reshape(nbc * b, nk * b)
    if use_interpret():
        # interpret-mode Pallas is trace-hostile
        p2 = jnp.dot(a2, b2, precision=jax.lax.Precision.HIGHEST)
    else:
        p2 = block_gemm_pallas(a2, b2)
    return p2.reshape(nbr, b, nk, b).transpose(2, 0, 1, 3)


def pselinv_round_gemm(Ainv, Uh, cmask):
    """Masked sweep GEMM keyed by a *round* of the overlapped stream: the
    struct mask arrives per round boundary (whatever elimination-tree
    level fires there), not per Python-level loop iteration.

    Ainv: (nbr, nbc, b, b) local A⁻¹ grid; Uh: (nk, nbc, b, b) raw Û
    stack straight out of the comm arena; cmask: (nk, nbc) struct mask of
    the firing level. Returns (nk, nbr, b, b) partial products through
    the same tiled-matmul core as :func:`pselinv_level_gemm`."""
    return pselinv_level_gemm(Ainv, Uh * cmask[:, :, None, None])


def flash_attention(q, k, v, causal=True):
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=use_interpret())


def rmsnorm(x, scale, eps=1e-5):
    return rmsnorm_pallas(x, scale, eps=eps, interpret=use_interpret())


def trsm(b, u):
    return trsm_pallas(b, u, interpret=use_interpret())
