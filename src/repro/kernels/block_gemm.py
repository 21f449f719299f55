"""Tiled GEMM Pallas kernel — the supernodal-GEMM hot spot of PSelInv
(step 3 of Alg. 1: A⁻¹(C,C)·L̂(C,K)), MXU-aligned 128×128×128 tiles with a
VMEM f32 accumulator across the K grid dimension."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_gemm_pallas"]


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_tiles: int,
                 alpha: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: f32 operands take full-precision MXU passes, not one
    # bf16 pass
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_tiles - 1)
    def _done():
        o_ref[...] = (alpha * acc_ref[...]).astype(o_ref.dtype)


def _pad_to(x, mult, axes):
    pads = [(0, 0)] * x.ndim
    needs = False
    for ax in axes:
        rem = (-x.shape[ax]) % mult
        if rem:
            pads[ax] = (0, rem)
            needs = True
    return jnp.pad(x, pads) if needs else x


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "alpha", "interpret"))
def block_gemm_pallas(a: jnp.ndarray, b: jnp.ndarray, bm: int = 128,
                      bn: int = 128, bk: int = 128, alpha: float = 1.0,
                      interpret: bool = False) -> jnp.ndarray:
    """alpha * (a @ b); shapes padded up to tile multiples."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    ap = _pad_to(a, max(bm, bk), (0, 1))[: ((m + bm - 1) // bm) * bm,
                                         : ((k + bk - 1) // bk) * bk]
    bp = _pad_to(b, max(bk, bn), (0, 1))[: ((k + bk - 1) // bk) * bk,
                                         : ((n + bn - 1) // bn) * bn]
    mp, kp = ap.shape
    _, np_ = bp.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, k_tiles=grid[2], alpha=alpha),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        # inside shard_map the output varies over the operands' mesh axes
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), a.dtype,
            vma=jax.typeof(ap).vma | jax.typeof(bp).vma),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
