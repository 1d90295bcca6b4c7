"""POM-scheduled tiled matmul Pallas kernel.

The paper's GEMM schedule (tile i/j/k, pipeline the outer tile loops, unroll
intra-tile loops, partition arrays) maps to:

  grid = (M/bm, N/bn, K/bk)       # pipelined outer loops (Mosaic pipeline)
  BlockSpec tiles                  # array partitioning (HBM->VMEM windows)
  one jnp.dot per block            # fully-unrolled intra-tile band on the MXU
  f32 VMEM accumulator scratch     # the recurrence register of the reduction

Block sizes come from ``autotune.pom_matmul_schedule`` — the stage-2 DSE
running on the TPU roofline model (minimise HBM traffic under the VMEM
budget, keep MXU dims 128-aligned).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jnp.ndarray, y: jnp.ndarray, *,
           bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (M, K) @ y: (K, N) -> (M, N); shapes padded to block multiples."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    yp = jnp.pad(y, ((0, pk), (0, pn))) if (pk or pn) else y
    M, K = xp.shape
    N = yp.shape[1]
    grid = (M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xp, yp)
    return out[:m, :n]
