"""Per-kernel interpret-mode validation against the pure-jnp oracles.

Shape/dtype sweeps + hypothesis property tests on kernel invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_rows
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.matmul_pom import matmul
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.stencil import jacobi2d


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 128, 384),
                                   (96, 64, 80), (128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, n, k, dtype):
    rng = np.random.default_rng(m + n + k)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    y = jnp.asarray(rng.normal(size=(k, n)), dtype)
    got = matmul(x, y, bm=64, bn=64, bk=64, interpret=True)
    want = ref.matmul(x, y)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=10, deadline=None)
@given(m=st.sampled_from([32, 64, 96]), n=st.sampled_from([32, 64]),
       k=st.sampled_from([32, 64, 128]), seed=st.integers(0, 2 ** 16))
def test_matmul_property(m, n, k, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    got = matmul(x, y, bm=32, bn=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ np.asarray(y),
                               rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------------------
# flash attention (prefill)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(hq, hkv, causal):
    b, s, d = 2, 128, 64
    rng = np.random.default_rng(hq * 10 + hkv)
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=64, bkv=64, interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,bq,bkv", [(256, 128, 64), (128, 32, 128)])
def test_flash_attention_blocks_dtypes(dtype, s, bq, bkv):
    b, h, d = 1, 2, 128
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    got = flash_attention(q, k, v, causal=True, bq=bq, bkv=bkv, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_prefill_suffix_alignment():
    """Sq < Skv: queries are the last Sq positions (chunked prefill)."""
    b, h, d, sq, skv = 1, 2, 32, 64, 128
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, skv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, skv, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=32, bkv=32, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------
def _rows(c):
    """A cache by head (B, Hkv, S, D) as the decode cache's rows
    (B, S, Hkv*D)."""
    b, hkv, s, d = c.shape
    return c.transpose(0, 2, 1, 3).reshape(b, s, hkv * d)


@pytest.mark.parametrize("hq,hkv,s", [(4, 4, 256), (8, 2, 512)])
def test_decode_attention(hq, hkv, s):
    b, d = 2, 64
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    got = decode_attention_rows(q, _rows(k), _rows(v), bkv=128,
                                interpret=True)
    want = ref.decode_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_ragged_lengths():
    b, hq, hkv, s, d = 3, 4, 2, 256, 32
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    length = jnp.array([17, 256, 130], jnp.int32)
    got = decode_attention_rows(q, _rows(k), _rows(v), length=length,
                                bkv=64, interpret=True)
    want = ref.decode_attention(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# chunked SSM scan
# --------------------------------------------------------------------------
@pytest.mark.parametrize("s,chunk", [(128, 32), (256, 64), (64, 64)])
def test_ssm_scan_chunked_vs_sequential(s, chunk):
    b, h, p, n = 2, 3, 16, 8
    rng = np.random.default_rng(s + chunk)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 1.0, size=(b, s, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    y, hl = ssm_scan(x, a, bb, c, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssm_scan(x, a, bb, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(h_ref),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_ssm_scan_state_composition(seed):
    """Invariant: scanning S tokens == scanning two halves with carried h."""
    b, s, h, p, n = 1, 64, 2, 8, 4
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.6, 1.0, size=(b, s, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    y_full, h_full = ref.ssm_scan(x, a, bb, c)
    half = s // 2
    y1, h1 = ref.ssm_scan(x[:, :half], a[:, :half], bb[:, :half], c[:, :half])
    y2, h2 = ref.ssm_scan(x[:, half:], a[:, half:], bb[:, half:], c[:, half:],
                          h0=h1)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# stencil
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,bm,steps", [(64, 48, 16, 1), (128, 64, 32, 3),
                                          (32, 32, 32, 2)])
def test_jacobi2d(m, n, bm, steps):
    rng = np.random.default_rng(m)
    x = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    got = jacobi2d(x, steps, bm=bm, interpret=True)
    want = ref.jacobi2d(x, steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# grouped matmul
# --------------------------------------------------------------------------
@pytest.mark.parametrize("e,cap,d,f", [(4, 64, 32, 48), (8, 128, 64, 64)])
def test_grouped_matmul(e, cap, d, f):
    rng = np.random.default_rng(e)
    x = jnp.asarray(rng.normal(size=(e, cap, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
    got = grouped_matmul(x, w, bm=32, bn=16, bk=16, interpret=True)
    want = ref.grouped_matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# autotuner (POM stage-2 on the TPU model)
# --------------------------------------------------------------------------
def test_pom_matmul_schedule_vmem_and_alignment():
    from repro.kernels.autotune import pom_matmul_schedule
    s = pom_matmul_schedule(4096, 4096, 4096, 2)
    assert s.vmem_bytes <= 16 * 2 ** 20
    assert s.bm % 128 == 0 and s.bn % 128 == 0 and s.bk % 128 == 0
    # large square matmul must be compute-bound with a good schedule
    assert s.terms.dominant == "compute"


def test_pom_attention_schedule_long_context():
    from repro.kernels.autotune import pom_attention_schedule
    s = pom_attention_schedule(8192, 8192, 128, 2, True)
    assert s.vmem_bytes <= 16 * 2 ** 20
    assert s.bq >= 128 and s.bkv >= 128
