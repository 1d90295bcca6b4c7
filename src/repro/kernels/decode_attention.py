"""Flash-decode Pallas kernel: one new token attending to a long KV cache.

Grid walks KV blocks sequentially per (batch x head); running (max, sum,
acc) live in VMEM scratch.  A per-row ``length`` masks the invalid cache
suffix, so the same kernel serves ragged batches.  The distributed layer
(`repro.distributed.sp`) shards the KV sequence across chips and merges the
per-chip (max, sum, acc) with psum -- the cross-chip half of the same
POM-chunked recurrence.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, nkv: int, bkv: int):
    h, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (1, d) -- single token row
    k = k_ref[0].astype(jnp.float32)            # (bkv, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1, bkv)
    kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    s = jnp.where(kpos < len_ref[h], s, NEG_INF)

    m_prev = m_ref[...]                                       # (1, 1)
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


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     length: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None, bkv: int = 256,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hq, D), k/v: (B, Hkv, S, D), length: (B,) -> (B, Hq, D).

    The per-row lengths ride in SMEM by scalar prefetch (a rank-1 VMEM
    block of one length per grid row would break the TPU tiling)."""
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bkv = min(bkv, s)
    assert s % bkv == 0
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    lengths = jnp.repeat(length.astype(jnp.int32), hq)     # (B*Hq,)

    qf = q.reshape(b * hq, 1, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    grid = (b * hq, s // bkv)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, nkv=grid[1], bkv=bkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda h, ik, lens: (h, 0, 0)),
                pl.BlockSpec((1, bkv, d),
                             lambda h, ik, lens, grp=group: (h // grp, ik, 0)),
                pl.BlockSpec((1, bkv, d),
                             lambda h, ik, lens, grp=group: (h // grp, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, d), lambda h, ik, lens: (h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths, qf, kf, vf)
    return out.reshape(b, hq, d)
