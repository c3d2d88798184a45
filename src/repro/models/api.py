"""Model registry: family dispatch + the uniform model bundle API.

Bundle contract (all functions pure):
* init(key) -> params
* loss_fn(params, batch) -> scalar  (batch: dict of arrays, no worker axis)
* forward(params, batch) -> logits
* loss_with_stats(params, batch) -> (scalar, {name: scalar})  (MoE: the
  routing counters; None elsewhere)
* init_cache(batch_size, max_len) -> cache      (decoder models only)
* decode_step(params, cache, tokens) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import dense, moe, rglru, xlstm

PyTree = Any


class ModelBundle(NamedTuple):
    config: ModelConfig
    init: Callable[[Any], PyTree]
    loss_fn: Callable[[PyTree, PyTree], jnp.ndarray]
    forward: Callable[[PyTree, PyTree], jnp.ndarray]
    init_cache: Optional[Callable[[int, int], PyTree]]
    decode_step: Optional[Callable[[PyTree, PyTree, jnp.ndarray], tuple]]
    loss_with_stats: Optional[Callable[[PyTree, PyTree], tuple]] = None


_FAMILIES = {
    "dense": dense,
    "moe": moe,
    "xlstm": xlstm,
    "rglru": rglru,
}


def build_model(cfg: ModelConfig) -> ModelBundle:
    mod = _FAMILIES[cfg.family]
    has_decode = cfg.has_decode and hasattr(mod, "decode_step")
    loss_with_stats = None
    if hasattr(mod, "loss_with_stats"):
        loss_with_stats = functools.partial(mod.loss_with_stats, cfg)
        loss_with_stats.stats = mod.STATS  # the counters' names, for the round
    return ModelBundle(
        config=cfg,
        init=functools.partial(mod.init_params, cfg),
        loss_fn=functools.partial(mod.loss_fn, cfg),
        forward=functools.partial(mod.forward, cfg),
        init_cache=functools.partial(mod.init_cache, cfg) if has_decode else None,
        decode_step=functools.partial(mod.decode_step, cfg) if has_decode else None,
        loss_with_stats=loss_with_stats,
    )


def param_count(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def active_param_count(cfg: ModelConfig, params: PyTree) -> int:
    """Active params per token (MoE: top_k + shared of the routed experts)."""
    total = param_count(params)
    if cfg.family != "moe" or not cfg.n_experts:
        return total
    # routed expert weights are 'wi'/'wo' under moe_blocks (the held ones)
    L_moe = cfg.n_layers - cfg.first_k_dense
    per_expert = 2 * cfg.moe_d_ff * cfg.d_model + cfg.moe_d_ff * cfg.d_model
    routed_total = L_moe * cfg.held_experts * per_expert
    routed_active = L_moe * cfg.top_k * per_expert * cfg.held_experts // cfg.n_experts
    return total - routed_total + routed_active


# ---------------------------------------------------------------------------
# batch specs (what each modality's training batch looks like)
# ---------------------------------------------------------------------------

def batch_spec(cfg: ModelConfig, batch: int, seq: int) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for one training batch (no worker axis)."""
    if cfg.modality == "audio":
        return {
            "features": jax.ShapeDtypeStruct((batch, seq, cfg.frontend_dim), jnp.float32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "mask": jax.ShapeDtypeStruct((batch, seq), jnp.bool_),
        }
    return {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}


def make_batch(cfg: ModelConfig, key, batch: int, seq: int) -> dict[str, jnp.ndarray]:
    """Random concrete batch matching batch_spec (for smoke tests)."""
    if cfg.modality == "audio":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "features": jax.random.normal(k1, (batch, seq, cfg.frontend_dim)),
            "labels": jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size),
            "mask": jax.random.bernoulli(k3, 0.5, (batch, seq)),
        }
    return {"tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)}
