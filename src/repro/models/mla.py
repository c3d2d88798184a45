"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1),
as the published ``modeling_deepseek`` computes it for training.

Per token: ``q = x W_q`` gives H heads of [q_nope (dn), q_pe (dr)] (no q
compression); ``x W_kva`` gives the latent c_kv (r) and one rope key k_pe
(dr) shared by all heads; c_kv goes through an RMSNorm (eps 1e-6) and
``W_kvb`` gives H heads of [k_nope (dn), v (dv)].  q_pe and k_pe rotate by
the YaRN-scaled rope; the causal softmax runs over [q_nope, q_pe] .
[k_nope, k_pe] at (dn + dr)^-1/2 times mscale^2; ``W_o`` maps the H x dv
outputs back.  Training forms the per-head k and v (no absorbed or latent
cache path).

Rope layout: pairs (i, i + dr/2) rotate together (``common.apply_rope``).
The published code stores the dr rope columns interleaved and permutes
them to this layout before rotating (evens first, then odds); with weights
drawn here that is a fixed relabelling of the rope columns, so the layout
used is the half-split one, in program and reference alike.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import common


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _correction_dim(rotations: float, dim: int, base: float, max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (2 * math.log(base))


def rope_frequencies(cfg: ModelConfig):
    """The (dr/2,) rotation frequencies: YaRN's blend of the base ones
    (extrapolated, fast dims) and the base ones over ``factor``
    (interpolated, slow dims), over a linear ramp between the correction
    dims of beta_fast and beta_slow."""
    dr = cfg.qk_rope_head_dim
    extra = common.rope_frequencies(dr, cfg.rope_theta)
    y = cfg.yarn
    if y is None:
        return extra
    low = max(math.floor(_correction_dim(y.beta_fast, dr, cfg.rope_theta,
                                         y.original_max_position)), 0)
    high = min(math.ceil(_correction_dim(y.beta_slow, dr, cfg.rope_theta,
                                         y.original_max_position)), dr - 1)
    ramp = jnp.clip(
        (jnp.arange(dr // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0
    )
    keep = 1.0 - ramp  # 1 where the base frequency is kept
    return extra / y.factor * (1.0 - keep) + extra * keep


def _rope_scale(cfg: ModelConfig) -> float:
    """The factor on YaRN's cos and sin (1 where mscale == mscale_all_dim)."""
    y = cfg.yarn
    if y is None:
        return 1.0
    return yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        m = yarn_mscale(y.factor, y.mscale_all_dim)
        s = s * m * m
    return s


def init_mla(cfg: ModelConfig, key, layers: Optional[int] = None):
    H, d = cfg.n_heads, cfg.d_model
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    L = (layers,) if layers else ()
    ks = jax.random.split(key, 4)
    return {
        "wq": common.dense_init(ks[0], L + (d, H * (dn + dr))),
        "wkv_a": common.dense_init(ks[1], L + (d, r + dr)),
        "kv_norm": jnp.zeros(L + (r,), jnp.float32),
        "wkv_b": common.dense_init(ks[2], L + (r, H * (dn + dv))),
        "wo": common.dense_init(ks[3], L + (H * dv, d)),
    }


def mla_qkv(cfg: ModelConfig, p, x, positions):
    """x (B, S, d) -> q, k (B, S, H, dn + dr) and v (B, S, H, dv)."""
    B, S, _ = x.shape
    H, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, H, dn + dr)
    ckv = x @ p["wkv_a"].astype(dt)
    c, k_pe = ckv[..., :r], ckv[..., r:]
    kv = (common.rmsnorm(c, p["kv_norm"]) @ p["wkv_b"].astype(dt)).reshape(B, S, H, dn + dv)
    freqs = rope_frequencies(cfg)
    q_pe = common.apply_rope(q[..., dn:], positions, cfg.rope_theta, freqs)
    k_pe = common.apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta, freqs)
    scale = _rope_scale(cfg)
    if scale != 1.0:
        q_pe, k_pe = q_pe * scale, k_pe * scale
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
    return q, k, kv[..., dn:]
