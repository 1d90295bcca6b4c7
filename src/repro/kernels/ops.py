"""Public jit'd wrappers over the Pallas kernels (the ``ops.py`` contract).

Every op takes ``schedule='pom' | 'naive'`` (POM-DSE block shapes vs fixed
defaults) and ``impl='pallas' | 'ref'``.  The models pick ``impl='pallas'``
when their config sets ``use_pallas`` and ``impl='ref'`` (pure jnp)
otherwise.  ``interpret=None`` leaves the choice to
``repro.runtime.pallas_interpret()``: compiled on an accelerator,
interpreted on the CPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .autotune import pom_attention_schedule, pom_matmul_schedule, pom_scan_schedule
from .decode_attention import decode_attention_rows as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .grouped_matmul import grouped_matmul as _gmm_pallas
from .matmul_pom import matmul as _matmul_pallas
from .ssm_scan import ssm_scan as _ssm_pallas
from .stencil import jacobi2d as _jacobi_pallas

Impl = str  # 'pallas' | 'ref'


def matmul(x, y, *, schedule: str = "pom", impl: Impl = "ref",
           interpret: Optional[bool] = None):
    if impl == "ref":
        return ref.matmul(x, y)
    m, k = x.shape
    n = y.shape[1]
    if schedule == "pom":
        s = pom_matmul_schedule(max(m, 128), max(n, 128), max(k, 128),
                                jnp.dtype(x.dtype).itemsize)
        bm, bn, bk = s.bm, s.bn, s.bk
    else:
        bm = bn = bk = 128
    return _matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, interpret=interpret)


def attention(q, k, v, *, causal: bool = True, scale=None,
              schedule: str = "pom", impl: Impl = "ref",
              interpret: Optional[bool] = None):
    if impl == "ref":
        return ref.attention(q, k, v, causal=causal, scale=scale)
    sq, skv, d = q.shape[2], k.shape[2], q.shape[3]
    if schedule == "pom":
        s = pom_attention_schedule(max(sq, 128), max(skv, 128), d,
                                   jnp.dtype(q.dtype).itemsize, causal)
        bq, bkv = s.bq, s.bkv
    else:
        bq = bkv = 128
    return _flash_pallas(q, k, v, causal=causal, scale=scale, bq=bq, bkv=bkv,
                         interpret=interpret)


def decode_attention(q, k, v, *, length=None, layer=None, scale=None,
                     schedule: str = "pom", impl: Impl = "ref",
                     interpret: Optional[bool] = None):
    """q: (B, Hq, D); k/v: the decode cache's rows (B, S, Hkv*D), or with
    ``layer`` a stack of them (Lyr, B, S, Hkv*D) and the layer read."""
    if impl == "ref":
        if layer is not None:
            k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        b, s, w = k.shape
        heads = lambda c: c.reshape(b, s, w // q.shape[-1], q.shape[-1]  # noqa: E731
                                    ).transpose(0, 2, 1, 3)
        return ref.decode_attention(q, heads(k), heads(v), length=length,
                                    scale=scale)
    skv, w = k.shape[-2:]              # a block holds whole rows of w values
    if schedule == "pom":
        s = pom_attention_schedule(128, max(skv, 128), w,
                                   jnp.dtype(q.dtype).itemsize, False)
        bkv = s.bkv
    else:
        bkv = 256
    return _decode_pallas(q, k, v, length=length, layer=layer, scale=scale,
                          bkv=bkv, interpret=interpret)


def ssm_scan(x, a, b, c, *, schedule: str = "pom", impl: Impl = "ref",
             interpret: Optional[bool] = None):
    if impl == "ref_chunked":
        # chunked pure-jnp path, python-unrolled (dry-run cost extraction)
        return ref.ssm_scan_chunked(x, a, b, c, unroll=True)
    if impl == "ref":
        return ref.ssm_scan(x, a, b, c)
    s, p, n = x.shape[1], x.shape[3], b.shape[3]
    if schedule == "pom":
        sc = pom_scan_schedule(max(s, 64), p, n, jnp.dtype(x.dtype).itemsize)
        chunk = sc.chunk
    else:
        chunk = 128
    return _ssm_pallas(x, a, b, c, chunk=chunk, interpret=interpret)


def jacobi2d(x, steps: int = 1, *, impl: Impl = "ref", interpret: Optional[bool] = None):
    if impl == "ref":
        return ref.jacobi2d(x, steps)
    return _jacobi_pallas(x, steps, interpret=interpret)


def grouped_matmul(x, w, *, schedule: str = "pom", impl: Impl = "ref",
                   interpret: Optional[bool] = None):
    if impl == "ref":
        return ref.grouped_matmul(x, w)
    e, cap, d = x.shape
    f = w.shape[2]
    if schedule == "pom":
        s = pom_matmul_schedule(max(cap, 128), max(f, 128), max(d, 128),
                                jnp.dtype(x.dtype).itemsize)
        bm, bn, bk = s.bm, s.bn, s.bk
    else:
        bm = bn = bk = 128
    return _gmm_pallas(x, w, bm=min(bm, cap), bn=min(bn, f), bk=min(bk, d),
                       interpret=interpret)
