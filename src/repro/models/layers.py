"""Shared model layers: RMSNorm, RoPE, GQA attention, SwiGLU, embeddings.

Pure-JAX pytree parameters (no flax): every layer is an ``init(key, cfg)``
returning a dict + an ``apply(params, x, ...)`` function.  Attention has two
execution paths: the Pallas kernels (TPU) and a chunked pure-jnp
flash-equivalent (XLA; bounded memory for 32k prefill so the multi-pod
dry-run can compile).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current
from repro.kernels import ops

Params = Dict


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# --------------------------------------------------------------------------
# norm / rope
# --------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, D) with D even; positions: (..., S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    while positions.ndim < x.ndim - 1:   # broadcast over head axes
        positions = positions[:, None] if positions.ndim > 1 else positions[None]
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# chunked causal attention (pure jnp, bounded memory) -- XLA path
# --------------------------------------------------------------------------
def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, chunk: int = 512,
                      unroll: bool = False,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax over q chunks.  q: (B,H,Sq,D), k/v: (B,Hkv,Skv,D).

    ``unroll=True`` python-loops the chunk scan (dry-run cost extraction:
    XLA cost_analysis counts lax.scan bodies once)."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    kr = jnp.repeat(k, group, axis=1) if group > 1 else k
    vr = jnp.repeat(v, group, axis=1) if group > 1 else v
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    chunk = min(chunk, sq)
    if sq % chunk:
        chunk = sq  # fallback for odd lengths (smoke tests)
    nq = sq // chunk
    off = skv - sq

    qc = q.reshape(b, h, nq, chunk, d)

    @functools.partial(jax.checkpoint, static_argnums=())
    def chunk_fn(qi, idx, kr, vr):
        # rematerialized in backward: the (chunk, Skv) score matrix is never
        # saved -- O(S) residuals instead of O(S^2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32),
                       kr.astype(jnp.float32)) * scale
        if causal:
            qpos = idx * chunk + jnp.arange(chunk)[:, None] + off
            kpos = jnp.arange(skv)[None, :]
            s = jnp.where(kpos <= qpos, s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32)) / \
            jnp.maximum(l, 1e-30)
        return o.astype(q.dtype)

    if unroll:
        outs = jnp.stack([chunk_fn(qc[:, :, i], jnp.int32(i), kr, vr)
                          for i in range(nq)])
    else:
        def body(carry, qi_idx):
            qi, idx = qi_idx
            return carry, chunk_fn(qi, idx, kr, vr)

        _, outs = jax.lax.scan(body, None,
                               (jnp.moveaxis(qc, 2, 0), jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 2).reshape(b, h, sq, d)
    return out


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    pdt = dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, h * hd), pdt) * std,
        "wk": jax.random.normal(k2, (d, kv * hd), pdt) * std,
        "wv": jax.random.normal(k3, (d, kv * hd), pdt) * std,
        "wo": jax.random.normal(k4, (h * hd, d), pdt) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), pdt)
        p["bk"] = jnp.zeros((kv * hd,), pdt)
        p["bv"] = jnp.zeros((kv * hd,), pdt)
    return p


def attention_scale(cfg: ModelConfig) -> float:
    """The scores' scale: the configured multiplier, else 1/sqrt(head_dim)."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return 1.0 / float(np.sqrt(cfg.resolved_head_dim))


def _qkv(p: Params, x: jnp.ndarray, cfg: ModelConfig, positions: jnp.ndarray):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.position_embedding == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    elif cfg.position_embedding != "nope":
        raise ValueError(f"position_embedding {cfg.position_embedding!r}")
    return q, k, v


def attention_prefill(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                      positions: jnp.ndarray):
    """Full (train/prefill) causal attention: (out (B,S,d), k, v), with
    k/v (B, S, KV*hd) the rows a decode cache holds."""
    b, s, d = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    scale = attention_scale(cfg)
    if cfg.use_pallas and s % 128 == 0:
        o = _flash_kernel(q, k, v, scale)
    else:
        o = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                              unroll=cfg.unroll_inner_scans, scale=scale)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return o @ p["wo"], _rows(k), _rows(v)


def _rows(c: jnp.ndarray) -> jnp.ndarray:
    """(B, KV, S, hd) -> the cache's rows (B, S, KV*hd)."""
    b, kv, s, hd = c.shape
    return c.transpose(0, 2, 1, 3).reshape(b, s, kv * hd)


def attention_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """Full (train/prefill) causal attention."""
    return attention_prefill(p, x, cfg, positions)[0]


def attention_decode(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     pos: jnp.ndarray, layer=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode. x: (B, 1, d); cache: rows (B, S, KV*hd), each
    position's K (or V) of every KV head side by side; pos: (B,).

    With ``layer``, the caches are a stack (Lyr, B, S, KV*hd) and this is
    layer ``layer`` of it: the new K/V row is written into the stack and
    the kernel reads the layer in place, so no layer is sliced out."""
    b, _, d = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, pos[:, None])
    if layer is None:
        cache_k, cache_v, at = cache_k[None], cache_v[None], 0
    else:
        at = layer
    # write each request's new k/v row at its position, in place
    rows = jnp.arange(b)
    cache_k = cache_k.at[at, rows, pos].set(_rows(k)[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[at, rows, pos].set(_rows(v)[:, 0].astype(cache_v.dtype))
    length = pos + 1
    scale = attention_scale(cfg)
    if cfg.use_pallas:
        o = _decode_kernel(q[:, :, 0, :], cache_k, cache_v, length, at, scale)
    else:
        o = ops.decode_attention(q[:, :, 0, :], cache_k, cache_v,
                                 length=length, layer=at, scale=scale,
                                 impl="ref")
    o = o.reshape(b, 1, cfg.num_heads * hd)
    if layer is None:
        cache_k, cache_v = cache_k[0], cache_v[0]
    return o @ p["wo"], cache_k, cache_v


def _mesh_axes(mc, rows: int, heads: int):
    """The mesh axes a kernel's requests and (KV) heads are split over:
    the batch axes and the model axis, each only where it divides."""
    bat, hds = mc.spec(("batch", "kv_heads"))

    def size(ax):
        axes = () if ax is None else (ax,) if isinstance(ax, str) else ax
        return int(np.prod([mc.mesh.shape[a] for a in axes]))

    return (bat if rows % size(bat) == 0 else None,
            hds if heads % size(hds) == 0 else None)


# GSPMD cannot partition a Pallas kernel: compiled, JAX refuses it ("Mosaic
# kernels cannot be automatically partitioned"); interpreted, GSPMD gathers
# the whole cache to every device.  So under a mesh each kernel is
# ``shard_map``'d by hand: requests over the batch axes, KV heads (and
# their query heads, a whole GQA group each) over the model axis.

def _flash_kernel(q, k, v, scale):
    """The Pallas flash kernel over q (B,H,S,hd), k/v (B,KV,S,hd)."""
    def run(q, k, v):
        return ops.attention(q, k, v, causal=True, scale=scale,
                             impl="pallas")
    mc = current()
    if mc is None:
        return run(q, k, v)
    bat, hds = _mesh_axes(mc, q.shape[0], k.shape[1])
    spec = P(bat, hds, None, None)
    return jax.shard_map(run, mesh=mc.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _decode_kernel(q, k, v, length, layer, scale):
    """The Pallas decode kernel over q (B,H,hd), a stack of row caches k/v
    (Lyr,B,S,KV*hd) and its layer ``layer``."""
    def run(q, k, v, n, at):
        return ops.decode_attention(q, k, v, length=n, layer=at, scale=scale,
                                    impl="pallas")
    layer = jnp.asarray(layer, jnp.int32)
    mc = current()
    if mc is None:
        return run(q, k, v, length, layer)
    bat, hds = _mesh_axes(mc, q.shape[0], k.shape[-1] // q.shape[-1])
    kv = P(None, bat, None, hds)
    return jax.shard_map(
        run, mesh=mc.mesh, in_specs=(P(bat, hds, None), kv, kv, P(bat), P()),
        out_specs=P(bat, hds, None),
        check_vma=False)(q, k, v, length, layer)  # pallas_call outputs carry no vma


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    pdt = dtype_of(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wi": jax.random.normal(k1, (d, f), pdt) * d ** -0.5,
        "wo": jax.random.normal(k3, (f, d), pdt) * f ** -0.5,
    }
    if cfg.mlp_gated:
        p["wg"] = jax.random.normal(k2, (d, f), pdt) * d ** -0.5
    return p


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "wg" in p:
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]


# --------------------------------------------------------------------------
# embeddings / unembedding
# --------------------------------------------------------------------------
def embed_init(key, cfg: ModelConfig) -> Params:
    pdt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab_size
    p = {"tok": jax.random.normal(k1, (v, cfg.d_model), pdt) * 0.02}
    if not cfg.tie_embeddings:
        p["out"] = jax.random.normal(k2, (v, cfg.d_model), pdt) * 0.02
    return p


def embed_apply(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed_apply(p: Params, x: jnp.ndarray, vocab_size: int,
                  compute_dtype=jnp.float32) -> jnp.ndarray:
    w = p.get("out", p["tok"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(compute_dtype),
                        w.astype(compute_dtype),
                        preferred_element_type=jnp.float32)
    vpad = w.shape[0]
    if vpad != vocab_size:
        mask = (jnp.arange(vpad) < vocab_size)
        logits = jnp.where(mask[None, None, :], logits, -1e30)
    return logits


# --------------------------------------------------------------------------
# modality frontend stubs (assignment: precomputed frame/patch embeddings)
# --------------------------------------------------------------------------
def frontend_apply(cfg: ModelConfig, embeddings: jnp.ndarray) -> jnp.ndarray:
    """Identity pass-through of precomputed embeddings: (B, S, d)."""
    assert embeddings.shape[-1] == cfg.d_model
    return embeddings
