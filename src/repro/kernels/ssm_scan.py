"""Chunked selective-scan (Mamba2-style SSD) Pallas kernel.

POM derivation (the paper's split+skew story applied to an SSM): the state
recurrence  h_t = a_t h_{t-1} + b_t (x) x_t  is a loop-carried dependence with
distance 1 -- unpipelineable as written (II = chain latency).  POM's *split*
of the time loop into (chunk, intra-chunk) plus reassociation turns the
intra-chunk band into dense matmuls (MXU work) and leaves only one carried
dependence per *chunk* (the h carry in VMEM scratch) -- II drops from S to
S/L sequential steps of large arithmetic intensity.

Semantics (per batch x head):
  within chunk: y[t] = sum_{s<=t} exp(cum[t]-cum[s]) * (c_t . b_s) x_s
                      + exp(cum[t]) * (c_t . h_prev)
  carry:        h    = B^T diag(exp(cum[L-1]-cum)) X + exp(cum[L-1]) h_prev
with cum = inclusive cumsum(log a); a in (0, 1] keeps all exponents <= 0.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret


def _ssm_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                nchunks: int, L: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    a = a_ref[0].astype(jnp.float32)          # (L,)
    b = b_ref[0].astype(jnp.float32)          # (L, N)
    c = c_ref[0].astype(jnp.float32)          # (L, N)
    h = h_ref[...]                            # (N, P)

    al = jnp.log(jnp.maximum(a, 1e-20))
    cum = jnp.cumsum(al)                      # (L,) inclusive

    # intra-chunk: masked decay matrix
    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    dt = cum[:, None] - cum[None, :]          # t, s
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    w = jnp.where(tri, jnp.exp(dt), 0.0) * g
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    c_dec = c * jnp.exp(cum)[:, None]
    y_inter = jax.lax.dot_general(c_dec, h, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # carry update
    w_in = jnp.exp(cum[L - 1] - cum)          # (L,)
    bw = b * w_in[:, None]                    # (L, N)
    h_new = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_ref[...] = h_new + jnp.exp(cum[L - 1]) * h

    @pl.when(ic == nchunks - 1)
    def _flush():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssm_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
             *, chunk: int = 128, interpret: Optional[bool] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,H,P), a: (B,S,H), b/c: (B,S,H,N) -> (y (B,S,H,P), h (B,H,N,P))."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nchunks = S // L

    # flatten (B, H) and make time the leading per-program axis
    xf = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    af = jnp.moveaxis(a, 2, 1).reshape(B * H, S)
    bf = jnp.moveaxis(b, 2, 1).reshape(B * H, S, N)
    cf = jnp.moveaxis(c, 2, 1).reshape(B * H, S, N)
    grid = (B * H, nchunks)

    y, h = pl.pallas_call(
        functools.partial(_ssm_kernel, nchunks=nchunks, L=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, P), lambda g, ic: (g, ic, 0)),
            pl.BlockSpec((1, L), lambda g, ic: (g, ic)),
            pl.BlockSpec((1, L, N), lambda g, ic: (g, ic, 0)),
            pl.BlockSpec((1, L, N), lambda g, ic: (g, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, P), lambda g, ic: (g, ic, 0)),
            pl.BlockSpec((1, N, P), lambda g, ic: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(xf, af, bf, cf)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    h = h.reshape(B, H, N, P)
    return y, h
