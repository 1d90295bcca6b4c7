"""POM stage-2 DSE applied to Pallas kernel schedules on the TPU model.

The same bottleneck-oriented search as ``core.dse.stage2``, specialised to
the kernel design space: block shapes (the TPU rendition of tile sizes /
array partitioning) under the VMEM resource constraint, scored by the
three-term roofline model instead of the XC7Z020 HLS model.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

from repro.core.cost_model import TPU_V5E, RooflineTerms, TpuModel, TpuSpec


@dataclass(frozen=True)
class MatmulSchedule:
    bm: int
    bn: int
    bk: int
    terms: RooflineTerms
    vmem_bytes: int


def _divisors_pow2(n: int, lo: int = 128, hi: int = 1024) -> List[int]:
    out = []
    b = lo
    while b <= min(n, hi):
        if n % b == 0:
            out.append(b)
        b *= 2
    return out or [min(n, lo)]


@functools.lru_cache(maxsize=4096)
def pom_matmul_schedule(m: int, n: int, k: int, dtype_bytes: int = 2,
                        spec: TpuSpec = TPU_V5E) -> MatmulSchedule:
    """Pick (bm, bn, bk) minimising the dominant roofline term.

    HBM traffic model: reads = m*k*(n/bn) + k*n*(m/bm), write = m*n.
    VMEM: (bm*bk + bk*bn)*dtype + bm*bn*4 (f32 acc), double buffered inputs.
    """
    model = TpuModel(spec)
    best: Optional[MatmulSchedule] = None
    for bm in _divisors_pow2(m):
        for bn in _divisors_pow2(n):
            for bk in _divisors_pow2(k, lo=128, hi=2048):
                vmem = 2 * (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4
                if vmem > spec.vmem_bytes:
                    continue
                reads = m * k * (n // bn) + k * n * (m // bm)
                bytes_total = (reads + m * n) * dtype_bytes
                terms = model.kernel_terms(2.0 * m * n * k, bytes_total)
                cand = MatmulSchedule(bm, bn, bk, terms, vmem)
                if best is None or cand.terms.bound_s < best.terms.bound_s:
                    best = cand
    assert best is not None
    return best


@dataclass(frozen=True)
class AttentionSchedule:
    bq: int
    bkv: int
    terms: RooflineTerms
    vmem_bytes: int


@functools.lru_cache(maxsize=4096)
def pom_attention_schedule(sq: int, skv: int, d: int, dtype_bytes: int = 2,
                           causal: bool = True,
                           spec: TpuSpec = TPU_V5E) -> AttentionSchedule:
    """Flash-attention block sizes: maximise bkv (fewer recurrence steps ==
    POM split factor) subject to VMEM; bq balances q reuse."""
    model = TpuModel(spec)
    best: Optional[AttentionSchedule] = None
    for bq in _divisors_pow2(sq, lo=128, hi=1024):
        for bkv in _divisors_pow2(skv, lo=128, hi=2048):
            # q, k, v blocks + acc + stats (f32)
            vmem = 2 * (bq * d + 2 * bkv * d) * dtype_bytes + bq * d * 4 + 2 * bq * 4
            if vmem > spec.vmem_bytes:
                continue
            frac = 0.5 if causal and sq == skv else 1.0
            flops = 4.0 * sq * skv * d * frac
            byts = (sq * d + 2 * skv * d * (sq // bq) * frac + sq * d) * dtype_bytes
            terms = model.kernel_terms(flops, byts)
            cand = AttentionSchedule(bq, bkv, terms, vmem)
            if best is None or cand.terms.bound_s < best.terms.bound_s:
                best = cand
    assert best is not None
    return best


@dataclass(frozen=True)
class ScanSchedule:
    chunk: int
    terms: RooflineTerms
    vmem_bytes: int


@functools.lru_cache(maxsize=4096)
def pom_scan_schedule(s: int, p: int, n: int, dtype_bytes: int = 2,
                      spec: TpuSpec = TPU_V5E) -> ScanSchedule:
    """Chunk length for the chunked SSM scan: the POM split factor.

    Larger chunks raise arithmetic intensity (L^2 work on L inputs) but the
    L x L decay matrix must fit VMEM; sequential chunk count S/L is the
    residual recurrence depth."""
    model = TpuModel(spec)
    best: Optional[ScanSchedule] = None
    L = 64
    while L <= min(s, 1024):
        if s % L == 0 and (L % 128 == 0 or L == s):
            vmem = (L * p + 2 * L * n) * dtype_bytes * 2 + L * L * 4 + n * p * 4
            if vmem <= spec.vmem_bytes:
                flops = 2.0 * s * (L * n + L * p + n * p)   # per (b,h): L^2-ish terms
                byts = s * (p + 2 * n + 1) * dtype_bytes + n * p * 4 * (s // L)
                terms = model.kernel_terms(flops, byts)
                cand = ScanSchedule(L, terms, vmem)
                if best is None or cand.terms.bound_s < best.terms.bound_s:
                    best = cand
        L *= 2
    assert best is not None
    return best
