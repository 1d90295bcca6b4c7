"""Flash-decode Pallas kernel: one new token attending to a long KV cache.

Grid walks KV blocks sequentially per request, every head of the request
at once; running (max, sum, acc) live in VMEM scratch.  A per-row
``length`` masks the invalid cache suffix, so the same kernel serves
ragged batches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import pallas_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, nkv: int, bkv: int):
    b, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * bkv < len_ref[b])          # blocks past the length: skipped
    def _block():
        q = q_ref[0].astype(jnp.float32)        # (Hq, W) block-diagonal rows
        k = k_ref[0, 0].astype(jnp.float32)     # (bkv, W) every KV head
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < len_ref[b], s, NEG_INF)       # (Hq, bkv)

        m_prev = m_ref[...]                                  # (Hq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def decode_attention_rows(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          length: Optional[jnp.ndarray] = None,
                          layer: Optional[jnp.ndarray] = None,
                          scale: Optional[float] = None, bkv: int = 256,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hq, D); k/v: a cache of rows (B, S, Hkv*D), each position's
    K (or V) of every KV head side by side; length: (B,) -> (B, Hq, D).

    One program per request and block of rows reads the block once for
    all its heads: the queries go in block-diagonal, each query head's row
    holding its D values in its KV head's columns, so one (Hq, W) x
    (W, bkv) product gives every head its own scores, and (Hq, bkv) x
    (bkv, W) then holds each head's output in its KV head's columns.
    A row of the cache is a whole row of the array, so a decode step
    writes it in place.  With ``layer`` (an int32 scalar), k/v are a
    stack (Lyr, B, S, Hkv*D) and the kernel reads layer ``layer`` of it,
    so a layer-scanned step never slices its cache out.  Lengths and the
    layer ride in SMEM by scalar prefetch; blocks past a request's length
    are neither fetched again nor computed."""
    if layer is None:
        k, v, layer = k[None], v[None], 0
    b, hq, d = q.shape
    n_layers, _, s, w = k.shape
    hkv = w // d
    assert hkv * d == w and hq % hkv == 0, (q.shape, k.shape)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bkv = min(bkv, s)
    assert s % bkv == 0
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    length = length.astype(jnp.int32)
    layers = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # (Hq, Hkv): query head h reads KV head h // group
    own = jnp.arange(hkv)[None, :] == (jnp.arange(hq) // group)[:, None]
    qbd = (q[:, :, None, :] * own[None, :, :, None].astype(q.dtype)
           ).reshape(b, hq, w)
    grid = (b, s // bkv)

    def kv_block(r, ik, lens, lay):
        last = jnp.maximum(lens[r] - 1, 0) // bkv   # past it: the same block
        return (lay[0], r, jnp.minimum(ik, last), 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, nkv=grid[1], bkv=bkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, hq, w), lambda r, ik, lens, lay: (r, 0, 0)),
                pl.BlockSpec((1, 1, bkv, w), kv_block),
                pl.BlockSpec((1, 1, bkv, w), kv_block),
            ],
            out_specs=pl.BlockSpec((1, hq, w),
                                   lambda r, ik, lens, lay: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, w), q.dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(length, layers, qbd, k, v)
    # each head's output sits in its own KV head's columns
    return jnp.einsum("bhgd,hg->bhd", out.reshape(b, hq, hkv, d),
                      own.astype(out.dtype))

