"""Unified model: init / forward / loss / KV-cache decode for all families.

Layers are stacked with ``jax.vmap`` at init and iterated with
``jax.lax.scan`` at apply time, so the HLO is one block regardless of depth
(fast 512-device compiles).  Heterogeneous stacks (MoE interleave, zamba2
shared attention, xLSTM sLSTM insertion) scan over *super-blocks* or use an
index-conditioned branch with shared (non-scanned) weights.

A config with ``layer_types`` is one stack driven by that per-layer
pattern: each layer is a Mamba-2 or an attention mixer and then the MLP,
and the stack scans over repeats of the pattern's period.  Its cache holds
two kinds of state side by side: K/V for the attention layers, SSM state
and conv window for the Mamba layers.  ``prefill`` fills a cache from whole
prompts in one forward pass (this stack and the dense family).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import telemetry
from repro.distributed.sharding import shard_hint
from . import layers as L
from . import mamba2 as M
from . import moe as MOE
from . import xlstm as XL

Params = Dict[str, Any]

# the prefill pads prompts to a multiple of this many positions, which every
# block size the Pallas flash and scan kernels choose divides
PREFILL_BLOCK = 128


def pattern(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int]:
    """(period, repeats) of ``cfg.layer_types``: the shortest prefix that
    makes the whole pattern by repetition."""
    lt = tuple(cfg.layer_types)
    if len(lt) != cfg.num_layers or set(lt) - {"mamba", "attention"}:
        raise ValueError(f"{cfg.name}: layer_types must name 'mamba' or "
                         f"'attention' for each of {cfg.num_layers} layers")
    for p in range(1, len(lt) + 1):
        if len(lt) % p == 0 and lt == lt[:p] * (len(lt) // p):
            return lt[:p], len(lt) // p


_MIXER_KEY = {"mamba": "mamba", "attention": "attn"}


def _scan(body, carry, xs, cfg: ModelConfig):
    """``lax.scan`` over the leading axis of ``xs``, or a Python loop over
    it when the config unrolls its layers."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, _layer_slice(xs, i))
        ys.append(y)
    return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _stacked(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(key, cfg: ModelConfig) -> Params:
    ke, kl, kn, ks = jax.random.split(key, 4)
    p: Params = {"embed": L.embed_init(ke, cfg),
                 "final_norm": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype))}

    if cfg.layer_types:
        period, repeats = pattern(cfg)

        def period_init(k):
            kk = jax.random.split(k, 2 * len(period))
            out = {}
            for i, kind in enumerate(period):
                mixer = (M.mamba2_init if kind == "mamba"
                         else L.attention_init)(kk[2 * i], cfg)
                out[f"l{i}"] = {
                    "ln1": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    _MIXER_KEY[kind]: mixer,
                    "ln2": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "mlp": L.mlp_init(kk[2 * i + 1], cfg)}
            return out
        p["blocks"] = _stacked(kl, repeats, period_init)

    elif cfg.family in ("dense", "audio", "vlm"):
        def block_init(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            return {"ln1": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "attn": L.attention_init(k1, cfg),
                    "ln2": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "mlp": L.mlp_init(k2, cfg)}
        p["blocks"] = _stacked(kl, cfg.num_layers, block_init)

    elif cfg.family == "moe":
        period = cfg.moe_every
        n_super = cfg.num_layers // period

        def super_init(k):
            kk = jax.random.split(k, period * 2)
            sub = []
            for i in range(period):
                k1, k2 = kk[2 * i], kk[2 * i + 1]
                is_moe = (i == period - 1)   # last layer of each super-block
                blk = {"ln1": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                       "attn": L.attention_init(k1, cfg),
                       "ln2": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype))}
                if is_moe:
                    blk["moe"] = MOE.moe_init(k2, cfg)
                else:
                    blk["mlp"] = L.mlp_init(k2, cfg)
                sub.append(blk)
            return {f"l{i}": s for i, s in enumerate(sub)}
        p["blocks"] = _stacked(kl, n_super, super_init)

    elif cfg.family == "hybrid":
        def block_init(k):
            return {"ln": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "mamba": M.mamba2_init(k, cfg)}
        p["blocks"] = _stacked(kl, cfg.num_layers, block_init)
        if cfg.attn_every:
            k1, k2 = jax.random.split(ks)
            p["shared_attn"] = {
                "ln1": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                "attn": L.attention_init(k1, cfg),
                "ln2": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                "mlp": L.mlp_init(k2, cfg)}

    elif cfg.family == "ssm":   # xLSTM
        period = cfg.slstm_every or cfg.num_layers + 1
        def block_init(k):
            k1, k2 = jax.random.split(k)
            return {"ln": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "mlstm": XL.mlstm_init(k1, cfg),
                    "ln_s": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg.param_dtype)),
                    "slstm": XL.slstm_init(k2, cfg)}
        p["blocks"] = _stacked(kl, cfg.num_layers, block_init)
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def _layer_slice(blocks, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], blocks)


def _remat(fn, cfg: ModelConfig):
    # all block fns take cfg at positional index 2 (static)
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy, static_argnums=(2,))
    return jax.checkpoint(fn, static_argnums=(2,))


def _dense_layer(bp, x, cfg, positions):
    """One dense layer: (x, its K/V rows {"k", "v"})."""
    o, k, v = L.attention_prefill(bp["attn"],
                                  L.rmsnorm(bp["ln1"], x, cfg.norm_eps),
                                  cfg, positions)
    x = x + o
    x = x + L.mlp_apply(bp["mlp"], L.rmsnorm(bp["ln2"], x, cfg.norm_eps))
    return shard_hint(x, ("batch", "seq", "embed")), {"k": k, "v": v}


def _dense_block(bp, x, cfg, positions):
    return _dense_layer(bp, x, cfg, positions)[0]


def _pattern_period(bp, x, cfg, positions, length=None):
    """One repeat of the layer pattern's period: each layer is
    ``x += r * mixer(norm(x)); x += r * mlp(norm(x))``, r the residual
    multiplier.  Returns (x, {l<i>: the layer's cache}): K/V rows of an
    attention layer, the state after ``length`` positions of a Mamba one."""
    period, _ = pattern(cfg)
    r = cfg.residual_multiplier
    caches = {}
    for i, kind in enumerate(period):
        blk = bp[f"l{i}"]
        h = L.rmsnorm(blk["ln1"], x, cfg.norm_eps)
        if kind == "attention":
            o, k, v = L.attention_prefill(blk["attn"], h, cfg, positions)
            caches[f"l{i}"] = {"k": k, "v": v}
        else:
            o, caches[f"l{i}"] = M.mamba2_apply(blk["mamba"], h, cfg, length)
        x = x + r * o
        x = x + r * L.mlp_apply(blk["mlp"], L.rmsnorm(blk["ln2"], x, cfg.norm_eps))
        x = shard_hint(x, ("batch", "seq", "embed"))
    return x, caches


def _pattern_block(bp, x, cfg, positions):
    return _pattern_period(bp, x, cfg, positions)[0]


def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = L.embed_apply(params["embed"], tokens).astype(L.dtype_of(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm, tied or own unembedding in ``logits_dtype``, and the
    logits' scaling: (B, S, V) fp32."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed_apply(params["embed"], x, cfg.vocab_size,
                             L.dtype_of(cfg.logits_dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _moe_super_block(bp, x, cfg, positions):
    aux_total = 0.0
    period = cfg.moe_every
    for i in range(period):
        blk = bp[f"l{i}"]
        x = x + L.attention_apply(blk["attn"],
                                  L.rmsnorm(blk["ln1"], x, cfg.norm_eps),
                                  cfg, positions)
        h = L.rmsnorm(blk["ln2"], x, cfg.norm_eps)
        if "moe" in blk:
            y, aux = MOE.moe_apply(blk["moe"], h, cfg)
            aux_total = aux_total + aux
        else:
            y = L.mlp_apply(blk["mlp"], h)
        x = x + y
        x = shard_hint(x, ("batch", "seq", "embed"))
    return x, aux_total


def _hybrid_block(bp, x, cfg, idx, shared, positions):
    x = x + M.mamba2_apply(bp["mamba"], L.rmsnorm(bp["ln"], x, cfg.norm_eps),
                           cfg)[0]
    if cfg.attn_every and shared is not None:
        def with_attn(x):
            return _dense_block(shared, x, cfg, positions)
        x = jax.lax.cond((idx + 1) % cfg.attn_every == 0, with_attn,
                         lambda x: x, x)
    return shard_hint(x, ("batch", "seq", "embed"))


def _xlstm_block(bp, x, cfg, idx):
    x = x + XL.mlstm_apply(bp["mlstm"], L.rmsnorm(bp["ln"], x, cfg.norm_eps), cfg)
    if cfg.slstm_every:
        def with_s(x):
            return x + XL.slstm_apply(bp["slstm"],
                                      L.rmsnorm(bp["ln_s"], x, cfg.norm_eps), cfg)
        x = jax.lax.cond((idx + 1) % cfg.slstm_every == 0, with_s,
                         lambda x: x, x)
    return shard_hint(x, ("batch", "seq", "embed"))


def forward(params: Params, cfg: ModelConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V) fp32, aux_loss scalar)."""
    if embeds is not None:
        x = L.frontend_apply(cfg, embeds).astype(L.dtype_of(cfg.dtype))
        b, s = x.shape[:2]
    else:
        x = _embed(params, cfg, tokens)
        b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = shard_hint(x, ("batch", "seq", "embed"))
    aux = jnp.zeros((), jnp.float32)

    if cfg.layer_types:
        def body(carry, bp):
            return _remat(_pattern_block, cfg)(bp, carry, cfg, positions), None
        x, _ = _scan(body, x, params["blocks"], cfg)

    elif cfg.family in ("dense", "audio", "vlm"):
        if cfg.scan_layers:
            def body(carry, bp):
                return _remat(_dense_block, cfg)(bp, carry, cfg, positions), None
            x, _ = jax.lax.scan(body, x, params["blocks"])
        else:
            for i in range(cfg.num_layers):
                x = _remat(_dense_block, cfg)(_layer_slice(params["blocks"], i),
                                              x, cfg, positions)

    elif cfg.family == "moe":
        if cfg.scan_layers:
            def body(carry, bp):
                x, aux = carry
                x, a = _remat(_moe_super_block, cfg)(bp, x, cfg, positions)
                return (x, aux + a), None
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
        else:
            for i in range(cfg.num_layers // cfg.moe_every):
                x, a = _remat(_moe_super_block, cfg)(
                    _layer_slice(params["blocks"], i), x, cfg, positions)
                aux = aux + a

    elif cfg.family == "hybrid":
        shared = params.get("shared_attn")
        if cfg.scan_layers:
            def body(carry, scanned):
                x, idx = carry
                bp = scanned
                fn = _remat(_hybrid_block, cfg)
                return (fn(bp, x, cfg, idx, shared, positions), idx + 1), None
            (x, _), _ = jax.lax.scan(body, (x, jnp.int32(0)), params["blocks"])
        else:
            for i in range(cfg.num_layers):
                bp = _layer_slice(params["blocks"], i)
                x = x + M.mamba2_apply(bp["mamba"],
                                       L.rmsnorm(bp["ln"], x, cfg.norm_eps),
                                       cfg)[0]
                if cfg.attn_every and shared is not None \
                        and (i + 1) % cfg.attn_every == 0:
                    x = _dense_block(shared, x, cfg, positions)
                x = shard_hint(x, ("batch", "seq", "embed"))

    elif cfg.family == "ssm":
        if cfg.scan_layers:
            def body(carry, bp):
                x, idx = carry
                fn = _remat(_xlstm_block, cfg)
                return (fn(bp, x, cfg, idx), idx + 1), None
            (x, _), _ = jax.lax.scan(body, (x, jnp.int32(0)), params["blocks"])
        else:
            for i in range(cfg.num_layers):
                bp = _layer_slice(params["blocks"], i)
                x = x + XL.mlstm_apply(bp["mlstm"],
                                       L.rmsnorm(bp["ln"], x, cfg.norm_eps), cfg)
                if cfg.slstm_every and (i + 1) % cfg.slstm_every == 0:
                    x = x + XL.slstm_apply(
                        bp["slstm"], L.rmsnorm(bp["ln_s"], x, cfg.norm_eps), cfg)
                x = shard_hint(x, ("batch", "seq", "embed"))

    logits = shard_hint(_unembed(params, cfg, x), ("batch", "seq", "vocab"))
    return logits, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, cfg, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"))
    labels = batch["labels"]
    v = logits.shape[-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, "ppl_log": loss}


# ---------------------------------------------------------------------------
# decode: cache init + single-token step
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Params:
    """The decode cache of ``batch`` requests up to ``max_seq`` positions.
    Records a ``model.cache`` telemetry event with its K/V bytes and its
    recurrent-state bytes."""
    cache = _init_cache(cfg, batch, max_seq, dtype)
    if telemetry.on():
        kv = state = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
            n = leaf.size * jnp.dtype(leaf.dtype).itemsize
            if getattr(path[-1], "key", None) in ("k", "v"):
                kv += n
            else:
                state += n
        telemetry.event("model.cache", "model", kv_bytes=int(kv),
                        state_bytes=int(state), batch=batch, max_seq=max_seq)
    return cache


def _init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Params:
    dt = dtype or L.dtype_of(cfg.dtype)
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads

    def kv(n):            # rows: each position's K (V) of every KV head
        return {"k": jnp.zeros((n, batch, max_seq, kvh * hd), dt),
                "v": jnp.zeros((n, batch, max_seq, kvh * hd), dt)}

    if cfg.layer_types:
        # two kinds of state side by side, each stacked over the repeats:
        # K/V at the attention positions, SSM state at the Mamba ones
        period, repeats = pattern(cfg)
        return {f"l{i}": kv(repeats) if kind == "attention" else
                jax.vmap(lambda _: M.mamba2_init_state(cfg, batch))(
                    jnp.arange(repeats))
                for i, kind in enumerate(period)}
    if cfg.family in ("dense", "audio", "vlm"):
        return kv(cfg.num_layers)
    if cfg.family == "moe":
        n_super = cfg.num_layers // cfg.moe_every
        return {f"l{i}": kv(n_super) for i in range(cfg.moe_every)}
    if cfg.family == "hybrid":
        st = jax.vmap(lambda _: M.mamba2_init_state(cfg, batch))(
            jnp.arange(cfg.num_layers))
        cache = {"ssm": st}
        if cfg.attn_every:
            cache["shared_kv"] = kv(cfg.num_layers // cfg.attn_every)
        return cache
    if cfg.family == "ssm":
        m = jax.vmap(lambda _: XL.mlstm_init_state(cfg, batch))(
            jnp.arange(cfg.num_layers))
        s = jax.vmap(lambda _: XL.slstm_init_state(cfg, batch))(
            jnp.arange(cfg.num_layers))
        return {"mlstm": m, "slstm": s}
    raise ValueError(cfg.family)


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                token: jnp.ndarray, pos: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """token: (B,) int32; pos: (B,) current positions. Returns (logits(B,V), cache)."""
    x = _embed(params, cfg, token[:, None])

    if cfg.layer_types:
        # K/V stacks ride in the carry and each attention layer writes its
        # row and reads its layer in place; SSM states go in and come out
        # layer by layer
        period, _ = pattern(cfg)
        r = cfg.residual_multiplier
        kv0 = {n: c for n, c in cache.items() if "k" in c}
        st0 = {n: c for n, c in cache.items() if "k" not in c}

        def body(carry, scanned):
            x, rep, kv = carry
            bp, st = scanned
            kv, new = dict(kv), {}
            for i, kind in enumerate(period):
                name, blk = f"l{i}", bp[f"l{i}"]
                h = L.rmsnorm(blk["ln1"], x, cfg.norm_eps)
                if kind == "attention":
                    o, ck, cv = L.attention_decode(
                        blk["attn"], h, cfg, kv[name]["k"], kv[name]["v"],
                        pos, layer=rep)
                    kv[name] = {"k": ck, "v": cv}
                else:
                    o, new[name] = M.mamba2_decode(blk["mamba"], h, st[name],
                                                   cfg)
                x = x + r * o
                x = x + r * L.mlp_apply(blk["mlp"],
                                        L.rmsnorm(blk["ln2"], x, cfg.norm_eps))
            return (x, rep + 1, kv), new
        (x, _, kv), st = _scan(body, (x, jnp.int32(0), kv0),
                               (params["blocks"], st0), cfg)
        cache = {**kv, **st}

    elif cfg.family in ("dense", "audio", "vlm"):
        def body(carry, bp):
            x, i, ks, vs = carry
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            o, ks, vs = L.attention_decode(bp["attn"], h, cfg, ks, vs, pos,
                                           layer=i)
            x = x + o
            x = x + L.mlp_apply(bp["mlp"], L.rmsnorm(bp["ln2"], x, cfg.norm_eps))
            return (x, i + 1, ks, vs), None
        (x, _, ks, vs), _ = _scan(body, (x, jnp.int32(0), cache["k"],
                                         cache["v"]), params["blocks"], cfg)
        cache = {"k": ks, "v": vs}

    elif cfg.family == "moe":
        period = cfg.moe_every
        def body(x, scanned):
            bp = scanned[0]
            caches = scanned[1]
            new_caches = {}
            for i in range(period):
                blk = bp[f"l{i}"]
                ck, cv = caches[f"l{i}"]["k"], caches[f"l{i}"]["v"]
                h = L.rmsnorm(blk["ln1"], x, cfg.norm_eps)
                o, ck, cv = L.attention_decode(blk["attn"], h, cfg, ck, cv, pos)
                x = x + o
                h2 = L.rmsnorm(blk["ln2"], x, cfg.norm_eps)
                if "moe" in blk:
                    y, _ = MOE.moe_apply(blk["moe"], h2, cfg)
                else:
                    y = L.mlp_apply(blk["mlp"], h2)
                x = x + y
                new_caches[f"l{i}"] = {"k": ck, "v": cv}
            return x, new_caches
        if cfg.scan_layers:
            x, new = jax.lax.scan(body, x, (params["blocks"], cache))
            cache = new
        else:
            outs = []
            for i in range(cfg.num_layers // period):
                x, nc = body(x, (_layer_slice(params["blocks"], i),
                                 jax.tree_util.tree_map(lambda c: c[i], cache)))
                outs.append(nc)
            cache = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    elif cfg.family == "hybrid":
        shared = params.get("shared_attn")
        has_attn = bool(cfg.attn_every) and shared is not None

        def body(carry, scanned):
            x, idx, skv = carry
            bp, st = scanned
            h = L.rmsnorm(bp["ln"], x, cfg.norm_eps)
            o, st = M.mamba2_decode(bp["mamba"], h, st, cfg)
            x = x + o
            if has_attn:
                def attn_branch(args):
                    x, skv = args
                    site = (idx + 1) // cfg.attn_every - 1
                    h = L.rmsnorm(shared["ln1"], x, cfg.norm_eps)
                    o, ck, cv = L.attention_decode(shared["attn"], h, cfg,
                                                   skv["k"], skv["v"], pos,
                                                   layer=site)
                    x = x + o
                    x = x + L.mlp_apply(
                        shared["mlp"], L.rmsnorm(shared["ln2"], x, cfg.norm_eps))
                    return x, {"k": ck, "v": cv}
                x, skv = jax.lax.cond((idx + 1) % cfg.attn_every == 0,
                                      attn_branch, lambda a: a, (x, skv))
            return (x, idx + 1, skv), st

        skv0 = cache.get("shared_kv",
                         {"k": jnp.zeros((0,)), "v": jnp.zeros((0,))})
        if cfg.scan_layers:
            (x, _, skv), st = jax.lax.scan(body, (x, jnp.int32(0), skv0),
                                           (params["blocks"], cache["ssm"]))
        else:
            skv = skv0
            sts = []
            site = 0
            for i in range(cfg.num_layers):
                bp = _layer_slice(params["blocks"], i)
                st_i = jax.tree_util.tree_map(lambda c: c[i], cache["ssm"])
                h = L.rmsnorm(bp["ln"], x, cfg.norm_eps)
                o, st_i = M.mamba2_decode(bp["mamba"], h, st_i, cfg)
                x = x + o
                if has_attn and (i + 1) % cfg.attn_every == 0:
                    h = L.rmsnorm(shared["ln1"], x, cfg.norm_eps)
                    o, ck, cv = L.attention_decode(shared["attn"], h, cfg,
                                                   skv["k"], skv["v"], pos,
                                                   layer=site)
                    x = x + o
                    x = x + L.mlp_apply(shared["mlp"],
                                        L.rmsnorm(shared["ln2"], x, cfg.norm_eps))
                    skv = {"k": ck, "v": cv}
                    site += 1
                sts.append(st_i)
            st = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sts)
        cache = {"ssm": st}
        if has_attn:
            cache["shared_kv"] = skv

    elif cfg.family == "ssm":
        def body(carry, scanned):
            x, idx = carry
            bp, mst, sst = scanned
            h = L.rmsnorm(bp["ln"], x, cfg.norm_eps)
            o, mst = XL.mlstm_decode(bp["mlstm"], h, mst, cfg)
            x = x + o
            if cfg.slstm_every:
                def w(args):
                    x, sst = args
                    h = L.rmsnorm(bp["ln_s"], x, cfg.norm_eps)
                    o, sst = XL.slstm_decode(bp["slstm"], h, sst, cfg)
                    return x + o, sst
                x, sst = jax.lax.cond((idx + 1) % cfg.slstm_every == 0, w,
                                      lambda a: a, (x, sst))
            return (x, idx + 1), (mst, sst)
        if cfg.scan_layers:
            (x, _), (m, s) = jax.lax.scan(body, (x, jnp.int32(0)),
                                          (params["blocks"], cache["mlstm"],
                                           cache["slstm"]))
            cache = {"mlstm": m, "slstm": s}
        else:
            ms, ss = [], []
            for i in range(cfg.num_layers):
                bp = _layer_slice(params["blocks"], i)
                mst = jax.tree_util.tree_map(lambda c: c[i], cache["mlstm"])
                sst = jax.tree_util.tree_map(lambda c: c[i], cache["slstm"])
                h = L.rmsnorm(bp["ln"], x, cfg.norm_eps)
                o, mst = XL.mlstm_decode(bp["mlstm"], h, mst, cfg)
                x = x + o
                if cfg.slstm_every and (i + 1) % cfg.slstm_every == 0:
                    h = L.rmsnorm(bp["ln_s"], x, cfg.norm_eps)
                    o, sst = XL.slstm_decode(bp["slstm"], h, sst, cfg)
                    x = x + o
                ms.append(mst)
                ss.append(sst)
            cache = {"mlstm": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ms),
                     "slstm": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ss)}
    else:
        raise ValueError(cfg.family)

    return _unembed(params, cfg, x)[:, 0, :], cache


# ---------------------------------------------------------------------------
# prefill: a whole prompt in one forward pass
# ---------------------------------------------------------------------------
def prefills(cfg: ModelConfig) -> bool:
    """Whether ``prefill`` covers this config's stack; the others fill a
    cache by teacher-forced ``decode_step`` calls."""
    return bool(cfg.layer_types) or cfg.family in ("dense", "audio", "vlm")


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Params, start=0) -> Tuple[jnp.ndarray, Params]:
    """One forward pass over prompts ``tokens`` (B, S), each from position
    0, that fills rows ``start`` .. ``start + B - 1`` of ``cache``
    (``init_cache``'s layout, ``max_seq >= S``): the attention layers'
    K/V rows 0 .. S-1, each Mamba layer's state after S tokens (the scan's
    final h and the last CONV_W-1 rows of xBC).  The prompt is padded to a
    multiple of PREFILL_BLOCK positions, which attention's causal mask and
    a zero dt at the pads keep out of every result.  Returns (logits
    (B, S, V) fp32, cache)."""
    if not prefills(cfg):
        raise NotImplementedError(f"{cfg.name}: prefill covers layer patterns "
                                  "and the dense family")
    b, s = tokens.shape
    sp = -(-s // PREFILL_BLOCK) * PREFILL_BLOCK
    x = shard_hint(_embed(params, cfg, jnp.pad(tokens, ((0, 0), (0, sp - s)))),
                   ("batch", "seq", "embed"))
    positions = jnp.broadcast_to(jnp.arange(sp)[None, :], (b, sp))
    if cfg.layer_types:
        def body(x, bp):
            return _pattern_period(bp, x, cfg, positions, s)
    else:
        def body(x, bp):
            return _dense_layer(bp, x, cfg, positions)
    x, layers = _scan(body, x, params["blocks"], cfg)

    def put(path, c, new):                 # new: (layers, B, ...) of a leaf
        if getattr(path[-1], "key", None) in ("k", "v"):
            new = new[:, :, :s]
        return jax.lax.dynamic_update_slice(
            c, new.astype(c.dtype), (0, start) + (0,) * (c.ndim - 2))
    cache = jax.tree_util.tree_map_with_path(put, cache, layers)
    return _unembed(params, cfg, x[:, :s]), cache
