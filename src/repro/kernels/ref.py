"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` of the contract).

Each function is the semantic ground truth the kernels are tested against in
interpret mode, and the fallback implementation models use on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(x.dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, scale: float | None = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D) with GQA broadcast."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if causal:
        skv = k.shape[2]
        # query i attends to keys j <= i + (skv - sq)  (aligned suffixes)
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        kj = jnp.arange(skv)[None, :]
        s = jnp.where(kj <= qi, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32)).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     length: jnp.ndarray | None = None,
                     scale: float | None = None) -> jnp.ndarray:
    """Single-token decode. q: (B, Hq, D), k/v: (B, Hkv, S, D).

    ``length``: (B,) valid KV prefix per batch row (None = full)."""
    b, hq, d = q.shape
    out = attention(q[:, :, None, :], k, v, causal=False,
                    scale=scale)[:, :, 0, :]
    if length is None:
        return out
    # masked variant
    hkv = k.shape[1]
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    mask = jnp.arange(k.shape[2])[None, None, :] < length[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, vr.astype(jnp.float32)).astype(q.dtype)


def _per_head(m: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(B, S, G, N) grouped b or c -> (B, S, H, N), each group repeated
    over its heads."""
    return jnp.repeat(m, heads // m.shape[2], axis=2)


def ssm_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, h0: jnp.ndarray | None = None):
    """Mamba2-style selective scan (scalar decay per head).

    x: (B, S, H, P)   inputs
    a: (B, S, H)      decay in (0, 1] (already exp(-softplus(...)dt))
    b: (B, S, G, N)   input projection to state, G groups of H/G heads
    c: (B, S, G, N)   state readout
    returns y: (B, S, H, P), h_last: (B, H, N, P)

    h_t = a_t * h_{t-1} + b_t ⊗ x_t ;  y_t = c_t · h_t
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    b, c = _per_head(b, H), _per_head(c, H)
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    def step(h, inp):
        xt, at, bt, ct = inp
        h = at[..., None, None] * h + bt[..., :, None] * xt[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", ct, h)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(a, 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    h_last, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_last


def ssm_scan_chunked(x, a, b, c, h0=None, chunk: int = 128,
                     unroll: bool = False):
    """Chunked form of ``ssm_scan`` in pure jnp (same math as the Pallas
    kernel).  ``unroll=True`` python-loops chunks so XLA cost_analysis
    counts the full sequence (dry-run cost extraction)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    b, c = _per_head(b, H), _per_head(c, H)
    L = min(chunk, S)
    assert S % L == 0
    nchunks = S // L
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    def chunk_fn(h, xc, ac, bc, cc):
        # xc: (B,L,H,P) etc.
        al = jnp.log(jnp.maximum(ac.astype(jnp.float32), 1e-20))
        cum = jnp.cumsum(al, axis=1)                        # (B,L,H)
        g = jnp.einsum("blhn,bshn->bhls", cc.astype(jnp.float32),
                       bc.astype(jnp.float32))
        dt = cum[:, :, None, :] - cum[:, None, :, :]        # (B,L,S,H)
        dt = jnp.moveaxis(dt, 3, 1)                         # (B,H,L,S)
        tri = jnp.tril(jnp.ones((L, L), bool))
        w = jnp.where(tri[None, None], jnp.exp(dt), 0.0) * g
        y_intra = jnp.einsum("bhls,bshp->blhp", w, xc.astype(jnp.float32))
        c_dec = cc.astype(jnp.float32) * jnp.exp(cum)[..., None]
        y_inter = jnp.einsum("blhn,bhnp->blhp", c_dec, h)
        w_in = jnp.exp(cum[:, -1:, :] - cum)                # (B,L,H)
        bw = bc.astype(jnp.float32) * w_in[..., None]
        h_new = jnp.einsum("bshn,bshp->bhnp", bw, xc.astype(jnp.float32))
        h = h_new + jnp.exp(cum[:, -1, :])[..., None, None] * h
        return h, (y_intra + y_inter).astype(x.dtype)

    xs = x.reshape(B, nchunks, L, H, P)
    as_ = a.reshape(B, nchunks, L, H)
    bs = b.reshape(B, nchunks, L, H, N)
    cs = c.reshape(B, nchunks, L, H, N)
    if unroll:
        h = h0
        ys = []
        for i in range(nchunks):
            h, y = chunk_fn(h, xs[:, i], as_[:, i], bs[:, i], cs[:, i])
            ys.append(y)
        y = jnp.concatenate(ys, axis=1)
    else:
        def body(h, inp):
            xc, ac, bc, cc = inp
            return chunk_fn(h, xc, ac, bc, cc)
        h, ys = jax.lax.scan(
            body, h0, (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(as_, 1, 0),
                       jnp.moveaxis(bs, 1, 0), jnp.moveaxis(cs, 1, 0)))
        y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P)
    return y.reshape(B, S, H, P), h


def jacobi2d(x: jnp.ndarray, steps: int = 1) -> jnp.ndarray:
    """Jacobi 2D sweep: interior = 0.2*(N+S+E+W+C); boundary unchanged."""
    def one(a):
        interior = 0.2 * (a[:-2, 1:-1] + a[2:, 1:-1] + a[1:-1, :-2]
                          + a[1:-1, 2:] + a[1:-1, 1:-1])
        return a.at[1:-1, 1:-1].set(interior)

    for _ in range(steps):
        x = one(x)
    return x


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Per-expert matmul.  x: (E, cap, d), w: (E, d, f) -> (E, cap, f)."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)
