"""Dense transformer LM — covers qwen3-8b/4b, qwen2-7b, olmo-1b, chameleon-34b
(early-fusion VLM over a fused token vocabulary) and hubert-xlarge (encoder-
only audio backbone with a stubbed conv-feature frontend).

Layers are stacked with a leading L axis and executed with scan-over-layers
(compact HLO at any depth).  ``cfg.remat`` wraps the
block in jax.checkpoint for training-memory control.

THE one pipeline: every entry point takes an optional ``backend`` carrying
the model-axis hooks of ``repro.core.comm`` (default: ``tp.IDENTITY``, under
which every hook short-circuits to the identity and this file is a plain
single-device transformer).  Bound to a mesh backend with model axes — via
``tp.make_tp_loss`` — the SAME code runs Megatron-style on local parameter
shards: activations enter column-parallel matmuls through ``tp.copy_to_tp``,
leave row-parallel ones through ``tp.reduce_from_tp``, and the embedding /
cross-entropy are vocab-parallel.  There is no separate TP forward to drift
out of sync with this one.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import common, tp

PyTree = Any


def init_params(cfg: ModelConfig, key) -> PyTree:
    L = cfg.n_layers
    keys = jax.random.split(key, 8)
    blocks = {
        "attn": common.init_attn(cfg, keys[0], layers=L),
        "mlp": common.init_mlp(cfg, keys[1], layers=L),
    }
    if cfg.norm_type != "nonparam_ln":
        blocks["ln1"] = jnp.zeros((L, cfg.d_model), jnp.float32)
        blocks["ln2"] = jnp.zeros((L, cfg.d_model), jnp.float32)
    params = {"blocks": blocks}
    if cfg.modality == "audio":
        params["feature_proj"] = common.dense_init(keys[2], (cfg.frontend_dim, cfg.d_model))
        params["mask_embed"] = jnp.zeros((cfg.d_model,), jnp.float32)
        params["cls_head"] = common.dense_init(keys[3], (cfg.d_model, cfg.vocab_size))
    else:
        params["embed"] = common.embed_init(keys[2], (cfg.vocab_size, cfg.d_model))
        if not cfg.tie_embeddings:
            params["lm_head"] = common.dense_init(keys[3], (cfg.d_model, cfg.vocab_size))
    if cfg.norm_type != "nonparam_ln":
        params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return params


def _local_cfg(cfg: ModelConfig, attn_params) -> ModelConfig:
    """Per-shard view of the config: head counts scaled down to what the
    LOCAL column-parallel qkv projections produce (read off the shard's
    actual trailing dims, so the same code runs on full params too — there
    the derived counts equal the config's own)."""
    hd = cfg.resolved_head_dim
    hq = attn_params["wq"].shape[-1] // hd
    hkv = attn_params["wk"].shape[-1] // hd
    # pin head_dim: with fewer local heads, the derived d_model // n_heads
    # would no longer be the true per-head width
    return cfg.replace(n_heads=hq, n_kv_heads=hkv, head_dim=hd)


def _block(cfg: ModelConfig, backend, x, positions, bp):
    """One transformer block.  With model shards: column-parallel qkv (heads
    sharded), local attention on the shard's heads, row-parallel wo + psum;
    column-parallel mlp gate/up, row-parallel mlp down + psum.  Norms and
    the residual stream stay replicated.  With the identity hooks the
    region operators vanish and this is the plain block."""
    lcfg = _local_cfg(cfg, bp["attn"])
    h = common.apply_norm(cfg, x, bp.get("ln1"))
    h = tp.copy_to_tp(backend, h)
    q, k, v = common.qkv_project(lcfg, bp["attn"], h, positions)
    o = common.attention(lcfg, q, k, v)
    x = x + tp.reduce_from_tp(backend, common.attn_out(lcfg, bp["attn"], o))
    h = common.apply_norm(cfg, x, bp.get("ln2"))
    h = tp.copy_to_tp(backend, h)
    x = x + tp.reduce_from_tp(backend, common.mlp(cfg, bp["mlp"], h))
    return x


def backbone(cfg: ModelConfig, params, x, positions, backend=tp.IDENTITY):
    """Run the stacked blocks over embeddings x (B, S, d)."""
    block = functools.partial(_block, cfg, backend)
    if cfg.remat:
        block = jax.checkpoint(block, static_argnums=())

    def body(carry, bp):
        return block(carry, positions, bp), None

    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=cfg.unroll_layers)
    return common.apply_norm(cfg, x, params.get("final_norm"))


def forward(
    cfg: ModelConfig, params, batch, last_only: bool = False, backend=tp.IDENTITY
) -> jnp.ndarray:
    """Return logits (B, S, V); last_only => logits for the final position only
    (prefill-style serving: avoids materializing the full-vocab logits).

    With a model-sharded ``backend`` the params are local shards and the
    returned logits are vocab-sharded (B, S, V/TP) — ``loss_fn`` consumes
    them through the vocab-parallel CE."""
    if cfg.modality == "audio":
        feats = batch["features"].astype(cfg.dtype)
        # feature_proj is replicated by rule (its output is the residual
        # stream) — plain matmul
        x = feats @ params["feature_proj"].astype(cfg.dtype)
        if "mask" in batch:
            m = batch["mask"][..., None].astype(cfg.dtype)
            x = x * (1 - m) + params["mask_embed"].astype(cfg.dtype) * m
    else:
        x = tp.vocab_parallel_embed(backend, params["embed"], batch["tokens"]).astype(
            cfg.dtype
        )
    B, S = x.shape[:2]
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    x = backbone(cfg, params, x, positions, backend=backend)
    if last_only:
        x = x[:, -1:]
    # the head is column-parallel on vocab: psum the backward into the
    # replicated final norm / residual stream
    x = tp.copy_to_tp(backend, x)
    if cfg.modality == "audio":
        head = params["cls_head"]
    else:
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head.astype(x.dtype)


def loss_fn(cfg: ModelConfig, params, batch, backend=tp.IDENTITY) -> jnp.ndarray:
    logits = forward(cfg, params, batch, backend=backend)
    if cfg.modality == "audio":
        # HuBERT-style masked prediction: CE over cluster ids at masked frames.
        return tp.vocab_parallel_xent(
            backend, logits, batch["labels"], cfg.vocab_size, batch["mask"]
        )
    return tp.vocab_parallel_xent(
        backend, logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab_size
    )


# ---------------------------------------------------------------------------
# decode (KV cache)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> PyTree:
    hd = cfg.resolved_head_dim
    # sliding-window models only need a window-sized cache
    S = min(max_len, cfg.window) if cfg.window else max_len
    shape = (cfg.n_layers, batch_size, S, cfg.n_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),  # absolute position of next token
    }


def decode_step(cfg: ModelConfig, params, cache, tokens) -> tuple[jnp.ndarray, PyTree]:
    """tokens: (B, 1) -> logits (B, 1, V) and the updated cache.

    The cache ring-buffers over ``window`` for sliding-window models.
    """
    x = params["embed"][tokens].astype(cfg.dtype)
    pos = cache["pos"]
    positions = jnp.full(tokens.shape, pos, jnp.int32)
    S_cache = cache["k"].shape[2]
    slot = pos % S_cache if cfg.window else jnp.minimum(pos, S_cache - 1)

    def body(carry, layer):
        x = carry
        bp, kc, vc = layer
        h = common.apply_norm(cfg, x, bp.get("ln1"))
        q, k, v = common.qkv_project(cfg, bp["attn"], h, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, slot, axis=1)
        if cfg.window:
            # ring buffer: all slots valid once pos >= window
            o = common.decode_attention(q, kc, vc, jnp.minimum(pos, S_cache - 1))
            # mask handled by validity below: positions beyond pos are zeros at
            # start; for pos < window the natural <=pos mask applies because
            # slot == pos there.
        else:
            o = common.decode_attention(q, kc, vc, pos)
        x = x + common.attn_out(cfg, bp["attn"], o)
        h = common.apply_norm(cfg, x, bp.get("ln2"))
        x = x + common.mlp(cfg, bp["mlp"], h)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]), unroll=cfg.unroll_layers
    )
    x = common.apply_norm(cfg, x, params.get("final_norm"))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    new_cache = {"k": k_new, "v": v_new, "pos": pos + 1}
    return logits, new_cache


def paged_step(
    cfg: ModelConfig,
    params,
    k_pages,
    v_pages,
    page_table,
    pos,
    num_new,
    tokens,
    backend=tp.IDENTITY,
    *,
    prefill_self: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One mixed chunked-prefill + decode step against a paged KV cache.

    tokens: (B, C) — per slot, the next ``num_new[b] <= C`` tokens (prompt
    chunk for prefilling slots, the previously sampled token at column 0 for
    decoding slots, anything for idle slots with ``num_new[b] == 0``);
    k_pages/v_pages: (L, num_pages + 1, page_size, Hkv, hd) pools with page 0
    reserved as the null page (``serve.cache``); page_table: (B,
    pages_per_slot) int32; pos: (B,) tokens already cached per slot.

    Every shape is static — admission, eviction and the prefill/decode mix
    are runtime inputs (``page_table``/``pos``/``num_new``), so the engine's
    scheduler never recompiles, mirroring how the elastic participation mask
    is a runtime input of the training round.  Invalid token positions
    (column >= ``num_new[b]``) scatter their KV into the null page and their
    attention outputs are never read: the returned logits are those of each
    slot's LAST valid token (garbage for idle slots — the host discards
    them).

    ``prefill_self=True`` is the pure-prefill fast path — only sound when
    every slot with work has ``pos[b] == 0``, so the chunk attends only to
    itself: attention runs as plain causal self-attention through
    ``common.attention``, which dispatches to the Pallas flash kernel under
    ``cfg.attention_impl == 'pallas'``.  Mixed/continuation steps use
    ``common.paged_attention`` (per-slot positions, which the kernel's
    static alignment cannot express).

    Threads the SAME model-axis hooks as ``forward``: under a model-sharded
    ``backend`` the params are local shards, the returned logits are
    vocab-sharded (B, V/TP), and sampling goes through the vocab-parallel
    primitives in ``models.tp``.
    """
    B, C = tokens.shape
    page_size = k_pages.shape[2]
    pages_per_slot = page_table.shape[1]
    x = tp.vocab_parallel_embed(backend, params["embed"], tokens).astype(cfg.dtype)
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # (B, C)
    valid = jnp.arange(C, dtype=jnp.int32)[None] < num_new[:, None]
    page_idx = jnp.clip(positions // page_size, 0, pages_per_slot - 1)
    page_ids = jnp.where(
        valid, jnp.take_along_axis(page_table, page_idx, axis=1), 0
    )
    offsets = positions % page_size

    def body(carry, layer):
        x = carry
        bp, kc, vc = layer
        lcfg = _local_cfg(cfg, bp["attn"])
        h = common.apply_norm(cfg, x, bp.get("ln1"))
        h = tp.copy_to_tp(backend, h)
        q, k, v = common.qkv_project(lcfg, bp["attn"], h, positions)
        # valid tokens land in their mapped page; invalid ones pile up in
        # the null page, which no gather ever unmasks
        kc = kc.at[page_ids, offsets].set(k)
        vc = vc.at[page_ids, offsets].set(v)
        if prefill_self:
            o = common.attention(lcfg, q, k, v, causal=True, window=cfg.window)
        else:
            o = common.paged_attention(
                q, kc, vc, page_table, positions, window=cfg.window
            )
        x = x + tp.reduce_from_tp(backend, common.attn_out(lcfg, bp["attn"], o))
        h = common.apply_norm(cfg, x, bp.get("ln2"))
        h = tp.copy_to_tp(backend, h)
        x = x + tp.reduce_from_tp(backend, common.mlp(cfg, bp["mlp"], h))
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], k_pages, v_pages), unroll=cfg.unroll_layers
    )
    x = common.apply_norm(cfg, x, params.get("final_norm"))
    last = jnp.clip(num_new - 1, 0, C - 1)
    x = x[jnp.arange(B), last]  # (B, d): each slot's last valid hidden
    x = tp.copy_to_tp(backend, x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return logits, k_new, v_new
