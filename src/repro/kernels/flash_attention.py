"""Flash attention (prefill) Pallas kernel with native GQA.

POM derivation (DESIGN.md SS2): the softmax recurrence is a loop-carried
dependence along the KV dimension (distance 1).  POM's split transform turns
it into a *chunked* recurrence -- running (max, sum, acc) statistics carried
across KV blocks in VMEM scratch -- which is exactly online softmax; the KV
block loop is the pipelined grid dim, the within-block band is unrolled onto
the MXU/VPU.

GQA is handled in the BlockSpec index map (kv head = q head // group): KV
blocks are fetched once per group, not materialised repeated.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, nkv: int, bq: int, bkv: int,
                  seq_off: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0].astype(jnp.float32)              # (bkv, d)
    v = v_ref[0].astype(jnp.float32)              # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + seq_off
        kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: Optional[float] = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    Requires Sq % bq == 0 and Skv % bkv == 0 (callers pad); Hq % Hkv == 0.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    seq_off = skv - sq  # aligned suffix causal offset (prefill continuation)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    grid = (b * hq, sq // bq, skv // bkv)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          nkv=grid[2], bq=bq, bkv=bkv, seq_off=seq_off),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda h, iq, ik, grp=group: (h // grp, ik, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda h, iq, ik, grp=group: (h // grp, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=pallas_interpret() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
