"""Parameter / optimizer-state / cache partitioning rules.

``param_logical_axes`` assigns every parameter a tuple of *logical* axes by
its pytree path (MaxText-style); ``MeshContext.spec`` maps those to mesh
axes.  ``zero1_axes`` additionally shards optimizer moments over the data
axis (ZeRO-1): XLA then emits reduce-scatter(grad) + all-gather(param)
around the update -- the distributed-optimizer communication pattern.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import init_params
from .sharding import MeshContext

Logical = Tuple[Optional[str], ...]


def _axes_for(path: str, shape: Tuple[int, ...], cfg: ModelConfig) -> Logical:
    """Logical axes for a parameter, keyed by its path suffix."""
    nd = len(shape)
    # xLSTM has too few heads to TP-shard the inner projections: replicate
    tpless = cfg.family == "ssm"

    def t(*axes):
        return tuple(axes)

    if "embed/tok" in path or "embed/out" in path:
        return t("vocab", "embed")
    if path.endswith("router"):
        return t("embed", None)
    if "/moe/wi" in path or "/moe/wg" in path:
        return t("experts", "embed", "mlp")
    if "/moe/wo" in path:
        return t("experts", "mlp", "embed")
    if "shared/wi" in path or "shared/wg" in path:
        return t("embed", "mlp")
    if "shared/wo" in path:
        return t("mlp", "embed")
    if path.endswith(("attn/wq", "attn/wk", "attn/wv")):
        return t("embed", None) if tpless else t("embed", "heads")
    if path.endswith(("attn/bq", "attn/bk", "attn/bv")):
        return t(None) if tpless else t("heads")
    if path.endswith("attn/wo"):
        return t(None, "embed") if tpless else t("heads", "embed")
    if path.endswith(("mlp/wi", "mlp/wg")):
        return t("embed", "mlp")
    if path.endswith("mlp/wo"):
        return t("mlp", "embed")
    # mamba2
    if path.endswith("mamba/w_in"):
        return t("embed", "mlp")
    if path.endswith("mamba/conv"):
        return t(None, "mlp")
    if path.endswith("mamba/conv_b"):
        return t("mlp")
    if path.endswith(("mamba/a_log", "mamba/dt_bias", "mamba/d_skip")):
        return t("ssm_heads")
    if path.endswith("mamba/w_out"):
        return t("mlp", "embed")
    if path.endswith("mamba/norm/scale"):
        return t("mlp")
    # xlstm (replicated TP-wise; DP/ZeRO carry it)
    if "mlstm" in path or "slstm" in path:
        return tuple([None] * nd)
    # norms and anything else 1-d: replicate
    return tuple([None] * nd)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_logical_axes(cfg: ModelConfig):
    """Pytree (matching init_params) of logical-axis tuples.

    Stacked layer params have a leading 'layers' axis prepended.
    """
    shapes = jax.eval_shape(
        lambda k: init_params(k, cfg),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype))

    def assign(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        if ps.startswith("blocks/"):
            inner = _axes_for(ps, shape[1:], cfg)
            return ("layers",) + inner
        return _axes_for(ps, shape, cfg)

    return jax.tree_util.tree_map_with_path(assign, shapes)


def logical_to_sharding(logical_tree, mc: MeshContext, shapes=None):
    """Map logical-axis tuples to NamedShardings, dropping mesh axes that do
    not divide the corresponding dimension."""
    def conv(path, axes, leaf=None):
        if leaf is None:
            return mc.sharding(axes)
        spec = mc.spec(axes)
        fixed = []
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            size = np.prod([mc.mesh.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))])
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(mc.mesh, P(*fixed))

    if shapes is None:
        return jax.tree_util.tree_map_with_path(
            lambda p, a: conv(p, a), logical_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
    return jax.tree_util.tree_map_with_path(
        lambda p, a, l: conv(p, a, l), logical_tree, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def zero1_axes(logical_tree, shapes, data_size: int):
    """Add a 'data' shard on the first replicated, divisible axis of every
    moment tensor (ZeRO-1)."""
    def z(axes, leaf):
        axes = list(axes)
        for i, (ax, dim) in enumerate(zip(axes, leaf.shape)):
            if ax is None and dim % data_size == 0 and dim >= data_size:
                axes[i] = "zero"
                return tuple(axes)
        return tuple(axes)

    return jax.tree_util.tree_map(
        z, logical_tree, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def batch_shardings(cfg: ModelConfig, kind: str, mc: MeshContext) -> Dict:
    """Input shardings per shape kind."""
    if kind == "train" or kind == "prefill":
        out = {"labels": mc.sharding(("batch", "seq"))}
        if cfg.frontend:
            out["embeds"] = mc.sharding(("batch", "seq", "embed"))
        else:
            out["tokens"] = mc.sharding(("batch", "seq"))
        return out
    # decode: token + pos
    return {"token": mc.sharding(("batch",)),
            "pos": mc.sharding(("batch",))}


def cache_logical_axes(cfg: ModelConfig, long_context: bool = False):
    """Logical axes for the decode cache (init_cache structure)."""
    kv_seq = "kv_seq_sharded" if long_context else "kv_seq"

    def kv_axes():
        return {"k": ("layers", "batch", kv_seq, "kv_heads"),
                "v": ("layers", "batch", kv_seq, "kv_heads")}

    ssm = {"h": ("layers", "batch", "ssm_heads", None, None),
           "conv": ("layers", "batch", None, "mlp")}
    if cfg.layer_types:
        from repro.models.model import pattern
        period, _ = pattern(cfg)
        return {f"l{i}": kv_axes() if kind == "attention" else dict(ssm)
                for i, kind in enumerate(period)}
    if cfg.family in ("dense", "audio", "vlm"):
        return kv_axes()
    if cfg.family == "moe":
        return {f"l{i}": kv_axes() for i in range(cfg.moe_every)}
    if cfg.family == "hybrid":
        out = {"ssm": dict(ssm)}
        if cfg.attn_every:
            out["shared_kv"] = kv_axes()
        return out
    if cfg.family == "ssm":
        return {"mlstm": {"C": ("layers", "batch", None, None, None),
                          "n": ("layers", "batch", None, None)},
                "slstm": {"c": ("layers", "batch", None, None),
                          "n": ("layers", "batch", None)}}
    raise ValueError(cfg.family)
