"""Mamba-2 mixer (SSD, scalar decay per head), as published
(arXiv:2405.21060; the layer of granite-4.0-h and zamba2).

  in_proj -> [z, xBC, dt];  xBC <- silu(causal depthwise conv(xBC) + bias);
  xBC -> [x, B, C] (B, C in GROUPS groups of heads);
  dt <- softplus(dt + dt_bias);  A = -exp(A_log);
  h_t = exp(dt_t A) h_{t-1} + B_t (x) (dt_t x_t);  y_t = C_t . h_t + D x_t;
  out = out_proj(rmsnorm(y * silu(z)))   (the norm per group of d_inner)

POM connection: the state recurrence is the paper's tight loop-carried
dependence; a prompt runs through the chunked scan (``kernels.ssm_scan``,
POM's split-and-skew), which also returns the final state, and decode
keeps (h, conv window over xBC) and does O(1) work per token -- which is
what makes ``long_500k`` runnable for this family.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from .layers import dtype_of, rmsnorm_init

Params = Dict
CONV_W = 4
# groups of heads that share B and C: one in granite-4.0-h and zamba2, the
# configurations that use this mixer (the scan kernel takes any number)
GROUPS = 1
DECODE_SCOPE = "mamba2_decode"


def _sizes(cfg: ModelConfig):
    """(d_inner, heads, head_dim, groups, state, conv channels)."""
    din = cfg.ssm_inner
    nh = cfg.ssm_heads or cfg.num_heads
    ph = cfg.ssm_head_dim
    if nh * ph != din:
        raise ValueError(f"{cfg.name}: {nh} heads x {ph} != d_inner {din}")
    g, n = GROUPS, cfg.ssm_state
    return din, nh, ph, g, n, din + 2 * g * n


def mamba2_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    din, nh, _, _, _, conv_dim = _sizes(cfg)
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    # Mamba-2's initialisation: dt log-uniform in [1e-3, 1e-1] through the
    # softplus, A uniform in [1, 16]
    dt = jnp.exp(jax.random.uniform(ks[3], (nh,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_in": jax.random.normal(ks[0], (d, din + conv_dim + nh), pdt) * d ** -0.5,
        "conv": jax.random.normal(ks[1], (CONV_W, conv_dim), pdt) * CONV_W ** -0.5,
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(ks[4], (nh,), jnp.float32, 1.0, 16.0)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "w_out": jax.random.normal(ks[5], (din, d), pdt) * din ** -0.5,
        "norm": rmsnorm_init(din, pdt),
        "conv_b": jax.random.normal(ks[2], (conv_dim,), pdt) * 0.1,
    }


def _split_in(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    """in_proj of x (..., d) -> z, xBC, dt (pre-activation)."""
    din, nh, _, _, _, conv_dim = _sizes(cfg)
    zxbcdt = x @ p["w_in"]
    return (zxbcdt[..., :din], zxbcdt[..., din:din + conv_dim],
            zxbcdt[..., din + conv_dim:])


def _split_xbc(xbc: jnp.ndarray, cfg: ModelConfig):
    """Activated xBC (..., conv_dim) -> x (..., H, P), B and C (..., G, N)."""
    din, nh, ph, g, n, _ = _sizes(cfg)
    lead = xbc.shape[:-1]
    return (xbc[..., :din].reshape(*lead, nh, ph),
            xbc[..., din:din + g * n].reshape(*lead, g, n),
            xbc[..., din + g * n:].reshape(*lead, g, n))


def _dt_decay(p: Params, dt_raw: jnp.ndarray):
    """dt = softplus(dt + dt_bias) and the decay exp(dt A), A = -exp(A_log)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return dt, jnp.exp(-dt * jnp.exp(p["a_log"]))


def _gated_norm(p: Params, y: jnp.ndarray, z: jnp.ndarray, cfg: ModelConfig,
                dtype) -> jnp.ndarray:
    """rmsnorm(y * silu(z)) over each group's d_inner / groups, in f32."""
    g = GROUPS
    u = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ug = u.reshape(*u.shape[:-1], g, u.shape[-1] // g)
    ug = ug * jax.lax.rsqrt(jnp.mean(ug * ug, -1, keepdims=True) + cfg.norm_eps)
    return (ug.reshape(u.shape) * p["norm"]["scale"].astype(jnp.float32)
            ).astype(dtype)


def _causal_conv(xin: jnp.ndarray, p: Params) -> jnp.ndarray:
    """Depthwise causal conv of width CONV_W plus bias, f32. xin: (B, S, C)."""
    w = p["conv"].astype(jnp.float32)
    s = xin.shape[1]
    pads = jnp.pad(xin.astype(jnp.float32), ((0, 0), (CONV_W - 1, 0), (0, 0)))
    return sum(pads[:, i:i + s, :] * w[i] for i in range(CONV_W)) + \
        p["conv_b"].astype(jnp.float32)


def mamba2_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                 length: Optional[int] = None):
    """x: (B, S, d) -> (out (B, S, d), state after the first ``length``
    positions: {"h": (B, H, P, N) f32, "conv": (B, CONV_W-1, C) f32}).

    Positions from ``length`` on (all of S by default) are padding: their
    dt is zero, so they leave the state as it was (decay 1, no input)."""
    b, s, _ = x.shape
    length = s if length is None else length
    z, xbc, dt_raw = _split_in(p, x, cfg)
    xs, bm, cm = _split_xbc(jax.nn.silu(_causal_conv(xbc, p)), cfg)
    dt, a = _dt_decay(p, dt_raw)
    if length < s:
        valid = (jnp.arange(s) < length)[None, :, None]
        dt, a = jnp.where(valid, dt, 0.0), jnp.where(valid, a, 1.0)

    if cfg.use_pallas and s % 64 == 0:
        impl = "pallas"
    elif cfg.unroll_inner_scans and s % 128 == 0:
        impl = "ref_chunked"
    else:
        impl = "ref"
    y, h = ops.ssm_scan(xs * dt[..., None], a, bm, cm, impl=impl)
    y = y + xs * p["d_skip"][:, None]
    out = _gated_norm(p, y.reshape(b, s, -1), z, cfg, x.dtype) @ p["w_out"]
    # the conv window a decode step continues from: the last CONV_W-1 rows
    # of xBC before ``length`` (zeros before the first token)
    window = jax.lax.slice_in_dim(
        jnp.pad(xbc, ((0, 0), (CONV_W - 1, 0), (0, 0))),
        length, length + CONV_W - 1, axis=1)
    return out, {"h": jnp.swapaxes(h, 2, 3).astype(jnp.float32),
                 "conv": window.astype(jnp.float32)}


# ---------------------------------------------------------------------------
# decode (single token, O(1) state)
# ---------------------------------------------------------------------------
def mamba2_init_state(cfg: ModelConfig, batch: int):
    """h is (B, H, P, N): the state size N, the wider, last, so that the
    chip keeps the array as it is laid out and the step needs no
    transpose of it."""
    _, nh, ph, _, n, conv_dim = _sizes(cfg)
    return {
        "h": jnp.zeros((batch, nh, ph, n), jnp.float32),
        "conv": jnp.zeros((batch, CONV_W - 1, conv_dim), jnp.float32),
    }


def mamba2_decode(p: Params, x: jnp.ndarray, state, cfg: ModelConfig):
    """x: (B, 1, d) -> (out (B,1,d), new_state)."""
    # every op of the step's mixer carries this scope in its op_name, so a
    # trace reader can tell the state update from the model's other work
    with jax.named_scope(DECODE_SCOPE):
        b = x.shape[0]
        nh = cfg.ssm_heads or cfg.num_heads
        z, xbc, dt_raw = _split_in(p, x[:, 0], cfg)
        window = jnp.concatenate(                           # (B, CONV_W, C)
            [state["conv"], xbc.astype(jnp.float32)[:, None]], axis=1)
        w = p["conv"].astype(jnp.float32)
        conv = sum(window[:, i] * w[i] for i in range(CONV_W)) + \
            p["conv_b"].astype(jnp.float32)
        # (B, H, P), (B, G, N), (B, G, N)
        xs, bm, cm = _split_xbc(jax.nn.silu(conv), cfg)
        dt, a = _dt_decay(p, dt_raw)                            # (B, H)
        per = nh // GROUPS
        bh = jnp.repeat(bm, per, axis=1)                        # (B, H, N)
        ch = jnp.repeat(cm, per, axis=1)
        h = state["h"] * a[:, :, None, None] + \
            (xs * dt[..., None])[:, :, :, None] * bh[:, :, None, :]
        y = jnp.einsum("bhn,bhpn->bhp", ch, h) + \
            xs * p["d_skip"][:, None]
        out = _gated_norm(p, y.reshape(b, -1), z, cfg, x.dtype) @ \
            p["w_out"]
        return out[:, None], {"h": h, "conv": window[:, 1:]}
