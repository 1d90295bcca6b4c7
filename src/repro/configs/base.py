"""Config system: model architecture, input shapes, parallelism.

Every assigned architecture registers a ``ModelConfig`` in ``REGISTRY`` via
its ``src/repro/configs/<id>.py`` module; shapes are the four assigned input
shapes; ``ParallelConfig`` holds the mesh/sharding knobs the launcher sets.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    mlp_gated: bool = True         # SwiGLU (True) vs GeLU 2-matrix (False)
    rope_theta: float = 10_000.0
    position_embedding: str = "rope"   # 'rope' | 'nope' (no position signal)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- muP multipliers (granite): embeddings x embedding_multiplier, each
    # residual branch x residual_multiplier, scores x attention_multiplier
    # (None: 1/sqrt(head_dim)), logits / logits_scaling ---
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # --- per-layer pattern: one 'mamba' or 'attention' mixer per layer, each
    # followed by the MLP; empty = the family's own fixed pattern ---
    layer_types: Tuple[str, ...] = ()
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_every: int = 1             # MoE layer every k-th layer (1 = all)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # --- SSM (Mamba2 / xLSTM) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    # --- hybrid (zamba2): shared attention block every k SSM blocks ---
    attn_every: int = 0
    # --- xLSTM: sLSTM block every k mLSTM blocks ---
    slstm_every: int = 0
    # --- modality frontend stubs (assignment: embeddings precomputed) ---
    frontend: Optional[str] = None  # 'encodec_frames' | 'clip_patches'
    # --- numerics / runtime ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    optim_state_dtype: str = "float32"   # first moment (m)
    optim_second_dtype: str = "float32"  # second moment (v)
    logits_dtype: str = "float32"        # unembed matmul precision
    remat: str = "full"            # 'none' | 'full' | 'dots'
    use_pallas: bool = False       # CPU container: pure-jnp path by default
    attn_chunk: int = 512          # chunked-attention q block (XLA path)
    scan_layers: bool = True       # lax.scan over the stack (False: unrolled —
                                   # used by the dry-run flops extrapolation)
    unroll_inner_scans: bool = False  # python-loop attention chunks / ssm
                                      # chunks so cost_analysis counts them
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def ssm_inner(self) -> int:
        """Width of a Mamba-2 mixer's inner stream (d_inner)."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_head_dim(self) -> int:
        """Width of a Mamba-2 head: d_inner over the heads."""
        return self.ssm_inner // (self.ssm_heads or self.num_heads)

    @property
    def padded_vocab_size(self) -> int:
        """Embedding tables padded to a TP-shardable multiple (128 lanes x
        16-way model axis); pad logits are masked to -inf in unembed."""
        m = 2048
        return -(-self.vocab_size // m) * m

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Exact total parameters via jax.eval_shape of the real init."""
        import jax
        from repro.models import init_params
        shapes = jax.eval_shape(
            lambda k: init_params(k, self),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
        return sum(int(s.size) for s in jax.tree_util.tree_leaves(shapes))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts count)."""
        if not self.num_experts:
            return self.param_count()
        d = self.d_model
        n_moe = self.num_layers // self.moe_every
        g = 3 if self.mlp_gated else 2
        inactive = n_moe * (self.num_experts - self.experts_per_token) * g * d * self.d_ff
        return self.param_count() - inactive


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # 'train' | 'prefill' | 'decode'


# the four assigned LM shapes (one set for all ten archs)
SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1
    tp: int = 1
    pods: int = 1
    zero1: bool = True             # shard optimizer state over data axis
    fsdp: bool = True              # shard params+grads over data axis too
    grad_compression: bool = False # int8 + error feedback DP sync
    seq_shard_decode: bool = True  # shard long KV over model axis (SP)
    pp_stages: int = 1             # GPipe over the pod axis when > 1
    microbatches: int = 1


ARCH_IDS = [
    "starcoder2_7b", "codeqwen1_5_7b", "smollm_360m", "qwen2_72b",
    "musicgen_large", "zamba2_1_2b", "llama4_maverick_400b",
    "granite_moe_1b", "xlstm_1_3b", "phi3_vision_4_2b",
    "granite_4_0_h_micro",
]

_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    name = name.replace("-", "_").replace(".", "_")
    if name not in _REGISTRY:
        importlib.import_module(f"repro.configs.{name}")
    return _REGISTRY[name]


def all_configs() -> Dict[str, ModelConfig]:
    for a in ARCH_IDS:
        get_config(a)
    return dict(_REGISTRY)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Smoke-test scale-down of the same family (assignment requirement)."""
    small = dict(
        num_layers=min(cfg.num_layers, 4 if cfg.family not in ("hybrid", "ssm") else 4),
        d_model=128,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads < cfg.num_heads else 4,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        head_dim=32,
        num_experts=min(cfg.num_experts, 4) if cfg.num_experts else 0,
        experts_per_token=min(cfg.experts_per_token, 2) if cfg.num_experts else 0,
        ssm_state=16 if cfg.ssm_state else 0,
        ssm_heads=4 if cfg.family in ("ssm", "hybrid") else 0,
        attn_every=2 if cfg.attn_every else 0,
        slstm_every=2 if cfg.slstm_every else 0,
        dtype="float32",
        param_dtype="float32",
        remat="none",
    )
    if cfg.layer_types:
        # each kind of layer, in the order it first appears, twice over:
        # both kinds present and the stack scanned over two repeats
        kinds = tuple(dict.fromkeys(cfg.layer_types))
        small.update(layer_types=kinds * 2, num_layers=2 * len(kinds))
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
