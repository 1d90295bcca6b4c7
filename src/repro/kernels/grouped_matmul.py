"""Grouped (per-expert) matmul Pallas kernel for MoE layers.

Capacity-based dispatch produces x: (E, cap, d); each expert has its own
weight (E, d, f).  The kernel is a batched POM-scheduled matmul whose
leading grid dim walks experts; expert weights stream HBM->VMEM once per
(expert, n-block) instead of being re-fetched per token block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (E, cap, d) @ w: (E, d, f) -> (E, cap, f)."""
    e, cap, d = x.shape
    _, _, f = w.shape
    bm, bn, bk = min(bm, cap), min(bn, f), min(bk, d)
    assert cap % bm == 0 and f % bn == 0 and d % bk == 0
    grid = (e, cap // bm, f // bn, d // bk)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda ee, i, j, kk: (ee, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda ee, i, j, kk: (ee, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ee, i, j, kk: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cap, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    )(x, w)
