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
    a = a_ref[0].astype(jnp.float32)          # (1, L)
    b = b_ref[0].astype(jnp.float32)          # (L, N)
    c = c_ref[0].astype(jnp.float32)          # (L, N)
    h = h_ref[...]                            # (N, P)

    # the inclusive cumsum of log a, as a column and as a row, by masked
    # reductions of an (L, L) broadcast (the TPU lowering has no cumsum)
    al = jnp.broadcast_to(jnp.log(jnp.maximum(a, 1e-20)), (L, L))  # [t, k]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = row >= col
    cum_c = jnp.sum(jnp.where(tri, al, 0.0), axis=1, keepdims=True)    # (L, 1)
    al_c = jnp.sum(jnp.where(row == col, al, 0.0), axis=1, keepdims=True)
    cum_r = jnp.sum(jnp.where(row <= col, jnp.broadcast_to(al_c, (L, L)), 0.0),
                    axis=0, keepdims=True)                              # (1, L)
    total = jnp.sum(al_c, axis=0, keepdims=True)                        # (1, 1)

    # intra-chunk: masked decay matrix
    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    w = jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0) * g
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    c_dec = c * jnp.exp(cum_c)
    y_inter = jax.lax.dot_general(c_dec, h, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # carry update
    bw_t = (b * jnp.exp(total - cum_c)).T     # (N, L)
    h_new = jax.lax.dot_general(bw_t, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_ref[...] = h_new + jnp.exp(total) * h

    @pl.when(ic == nchunks - 1)
    def _flush():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssm_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
             *, chunk: int = 128, interpret: Optional[bool] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,H,P), a: (B,S,H), b/c: (B,S,G,N) -> (y (B,S,H,P), h (B,H,N,P)).

    b and c come in G groups, each shared by H/G consecutive heads; the
    index map fetches a head's group, so they are never repeated per head."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    assert H % G == 0, (H, G)
    per = H // G
    # compiled, a chunk of the decays is a row of lanes: L a multiple of
    # 128 or all of S (``pom_scan_schedule`` picks such an L)
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nchunks = S // L

    # flatten (B, H) and make time the leading per-program axis
    xf = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    af = jnp.moveaxis(a, 2, 1).reshape(B * H, 1, S)
    bf = jnp.moveaxis(b, 2, 1).reshape(B * G, S, N)
    cf = jnp.moveaxis(c, 2, 1).reshape(B * G, S, N)
    grid = (B * H, nchunks)

    def group(g, ic):                 # program (batch, head) -> its group
        return ((g // H) * G + (g % H) // per, ic, 0)

    y, h = pl.pallas_call(
        functools.partial(_ssm_kernel, nchunks=nchunks, L=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, P), lambda g, ic: (g, ic, 0)),
            pl.BlockSpec((1, 1, L), lambda g, ic: (g, 0, ic)),
            pl.BlockSpec((1, L, N), group),
            pl.BlockSpec((1, L, N), group),
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
