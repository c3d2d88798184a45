"""Config system: model / input-shape / mesh / run configs and the registry.

Every assigned architecture gets one ``<arch>.py`` in this package exporting
``CONFIG`` (exact full-size spec, cited) and ``REDUCED`` (2-layer smoke-test
variant).  ``get_config(name)`` resolves dashed or underscored ids.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling``)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'xlstm' | 'rglru'
    modality: str = "text"  # 'text' | 'audio' | 'vlm'
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None
    # attention options
    causal: bool = True  # False => encoder-only (hubert)
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    window: Optional[int] = None  # sliding-window attention (all layers)
    # norms / embeddings
    norm_type: str = "rmsnorm"  # 'rmsnorm' | 'nonparam_ln'
    tie_embeddings: bool = False
    act: str = "swiglu"  # 'swiglu' | 'gelu'; swiglu params are DE-FUSED
    # (separate w_gate/w_up leaves, both column-parallel under TP — a fused
    # gate+up matrix would interleave columns across model shards)
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0  # leading dense layers before MoE starts
    dense_d_ff: int = 0  # d_ff of those leading dense layers
    experts_held: int = 0  # experts 0 .. H-1 this layer computes; 0 = all
    norm_topk_prob: bool = True  # renormalise the top-k gates to sum 1
    moe_aux: str = "switch"  # 'switch': E sum f_e p_e, top-1, in the loss;
    # 'seq': DeepSeek-V2's per-sequence balance loss, in the gradient only
    aux_loss_coef: float = 0.01
    # multi-head latent attention (DeepSeek-V2); the kind follows from
    # kv_lora_rank > 0.  No q compression: q comes from one projection.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional["Yarn"] = None  # YaRN scaling of the rope frequencies
    # xLSTM
    slstm_every: int = 0  # every k-th block is sLSTM (0 => all mLSTM)
    chunk_size: int = 256
    proj_factor: float = 2.0
    # RG-LRU hybrid
    pattern: tuple[str, ...] = ()  # e.g. ('rec', 'rec', 'attn')
    lru_width: Optional[int] = None
    conv_width: int = 4
    # frontends (audio/vlm stubs)
    frontend_dim: int = 0  # e.g. 512 for hubert conv features
    # compute
    dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"  # 'auto' | 'xla' | 'chunked' | 'pallas'
    unroll_layers: bool = False  # unroll scan-over-layers (cost analysis counts a loop once)
    attn_chunk: int = 1024  # kv-chunk for the chunked (online-softmax) impl
    source: str = ""  # citation

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def sub_quadratic(self) -> bool:
        """True if decode at 500k context is feasible (no full attention)."""
        return self.family in ("xlstm", "rglru") or self.window is not None

    @property
    def has_decode(self) -> bool:
        return self.causal  # encoder-only models have no decode step

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = [
    "kimi-k2-1t-a32b",
    "hubert-xlarge",
    "xlstm-1.3b",
    "qwen3-8b",
    "recurrentgemma-2b",
    "deepseek-moe-16b",
    "qwen2-7b",
    "olmo-1b",
    "chameleon-34b",
    "qwen3-4b",
    "deepseek-v2-lite",
]


def _modname(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_modname(arch_id)}")
    return mod.REDUCED if reduced else mod.CONFIG


def all_configs(reduced: bool = False) -> dict[str, ModelConfig]:
    return {a: get_config(a, reduced) for a in ARCH_IDS}
