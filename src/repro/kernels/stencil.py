"""Jacobi-2D stencil Pallas kernel (paper SS VII-F workloads).

POM analysis: the Jacobi update has *no* intra-step loop-carried dependence
(reads previous timestep only), so both spatial loops parallelise; the halo
rows are fetched by giving the kernel three row-block views of the input
(up / center / down) whose BlockSpec index maps are clamped at the grid
edge -- the BlockSpec rendition of `array_partition` with ghost zones.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret


def _jacobi_kernel(up_ref, c_ref, dn_ref, o_ref, *, bm: int, m: int, n: int):
    i = pl.program_id(0)
    cblk = c_ref[...].astype(jnp.float32)     # (bm, n)
    up = up_ref[...].astype(jnp.float32)
    dn = dn_ref[...].astype(jnp.float32)

    north = jnp.concatenate([up[-1:], cblk[:-1]], axis=0)
    south = jnp.concatenate([cblk[1:], dn[:1]], axis=0)
    west = jnp.concatenate([cblk[:, :1], cblk[:, :-1]], axis=1)
    east = jnp.concatenate([cblk[:, 1:], cblk[:, -1:]], axis=1)
    out = 0.2 * (north + south + west + east + cblk)

    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    interior = (row > 0) & (row < m - 1) & (col > 0) & (col < n - 1)
    o_ref[...] = jnp.where(interior, out, cblk).astype(o_ref.dtype)


def jacobi2d_step(x: jnp.ndarray, *, bm: int = 128,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """One Jacobi sweep over (M, N); boundary cells pass through."""
    m, n = x.shape
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    grid = (m // bm,)
    nblk = grid[0]
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, bm=bm, m=m, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i, nb=nblk: (jnp.minimum(i + 1, nb - 1), 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, x, x)


def jacobi2d(x: jnp.ndarray, steps: int = 1, *, bm: int = 128,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    for _ in range(steps):
        x = jacobi2d_step(x, bm=bm, interpret=interpret)
    return x
