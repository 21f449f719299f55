"""Right-side upper-triangular solve Pallas kernel:  X·U = B.

This is the PSelInv normalization hot spot (L̂(I,K) = L(I,K)·U(K,K)⁻¹,
Alg. 1 loop 1). Row tiles of B stream through VMEM; the full U block
(supernode width ≤ 256) stays VMEM-resident; forward substitution runs
column-by-column with ``fori_loop`` over dynamic VMEM slices."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["trsm_pallas"]


def _trsm_kernel(b_ref, u_ref, o_ref, *, k: int):
    u = u_ref[...].astype(jnp.float32)      # (k, k) upper
    b = b_ref[...].astype(jnp.float32)      # (bm, k)

    def col(j, x):
        # x[:, j] = (b[:, j] - Σ_{i<j} x[:, i]·u[i, j]) / u[j, j]
        mask = jax.lax.broadcasted_iota(jnp.int32, (k,), 0) < j
        uj = jnp.where(mask, u[:, j], 0.0)
        s = x @ uj                           # (bm,)
        xj = (jax.lax.dynamic_slice_in_dim(b, j, 1, axis=1)[:, 0] - s) \
            / u[j, j]
        return jax.lax.dynamic_update_slice_in_dim(
            x, xj[:, None], j, axis=1)

    x = jax.lax.fori_loop(0, k, col, jnp.zeros_like(b))
    o_ref[...] = x.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def trsm_pallas(b, u, bm: int = 128, interpret: bool = False):
    """Solve X·U = B; b: (m, k), u: (k, k) upper triangular."""
    m, k = b.shape
    assert u.shape == (k, k)
    bm = min(bm, m)
    pad = (-m) % bm
    bp = jnp.pad(b, ((0, pad), (0, 0))) if pad else b

    out = pl.pallas_call(
        functools.partial(_trsm_kernel, k=k),
        grid=(bp.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(bp.shape, b.dtype),
        interpret=interpret,
    )(bp, u)
    return out[:m]
