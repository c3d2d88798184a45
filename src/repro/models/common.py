"""Shared model layers (pure-functional, pytree params, no framework deps).

Conventions:
* activations (B, S, d); attention heads materialized as (B, S, H, hd);
* parameter leaves may carry a leading layer axis L for scan-over-layers;
* math in the config's compute dtype, norms/softmax/CE in fp32.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops as kops

PyTree = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-ish, standard for LMs)."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = fan_in**-0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale=None, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))  # scales stored zero-centered
    return y.astype(x.dtype)


def nonparam_layernorm(x, eps=1e-5):
    """OLMo-style non-parametric LayerNorm (no scale, no bias)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, x, scale):
    if cfg.norm_type == "nonparam_ln":
        return nonparam_layernorm(x)
    return rmsnorm(x, scale)


def init_norm(cfg: ModelConfig, key, width=None):
    if cfg.norm_type == "nonparam_ln":
        return None
    return jnp.zeros((width or cfg.d_model,), jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float, freqs=None):
    """x: (B, S, H, hd); positions: (B, S) or (S,) int32.  Pairs (i, i +
    hd/2) rotate by ``freqs`` (hd/2,), by default ``rope_frequencies``."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(hd, theta)
    if positions.ndim == 1:
        positions = positions[None]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (training + decode)
# ---------------------------------------------------------------------------

def _repeat_kv(k, group: int):
    return jnp.repeat(k, group, axis=2) if group > 1 else k


def attention_full(q, k, v, *, causal, window, q_offset=0, scale=None):
    """Materialized-logits attention (O(S^2) memory) — fine for short S.
    v's head dim may differ from q's and k's; ``scale`` defaults to
    D**-0.5 of the q.k dim D."""
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    group = Hq // k.shape[2]
    kf = _repeat_kv(k, group)
    vf = _repeat_kv(v, group)
    scale = D**-0.5 if scale is None else scale
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kf.astype(jnp.float32)
    )
    iq = jnp.arange(Sq)[:, None] + q_offset
    ik = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= ik <= iq
    if window is not None:
        mask &= ik > iq - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf.astype(jnp.float32)).astype(q.dtype)


def attention_chunked(q, k, v, *, causal, window, chunk=1024, scale=None):
    """Online-softmax attention in pure XLA ops: scan over kv chunks.

    Memory is O(Sq * chunk) instead of O(Sq * Skv) — this is the flash
    recurrence expressed at the XLA level, used for long sequences where the
    full score matrix would not fit.  The chunk
    step is checkpointed, so that the backward pass recomputes each chunk's
    scores instead of keeping them all: O(Sq * chunk) under autodiff too.
    """
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    group = Hq // k.shape[2]
    if Skv % chunk:
        pad = chunk - Skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    Dv = v.shape[-1]
    scale = D**-0.5 if scale is None else scale
    qf = q.astype(jnp.float32) * scale
    kc = k.reshape(B, n_chunks, chunk, k.shape[2], D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, v.shape[2], Dv).transpose(1, 0, 2, 3, 4)
    q_offset = Skv - Sq  # align sequence ends

    def body(carry, inp):
        m, l, acc, j = carry
        kj, vj = inp
        kj = _repeat_kv(kj, group).astype(jnp.float32)
        vj = _repeat_kv(vj, group).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj)
        rows = jnp.arange(Sq)[:, None] + q_offset
        cols = j * chunk + jnp.arange(chunk)[None, :]
        mask = cols < Skv
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vj)
        return (m_new, l_new, acc_new, j + 1), None

    body = jax.checkpoint(body)
    m0 = jnp.full((B, Hq, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), (kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def attention(cfg: ModelConfig, q, k, v, *, causal=None, window=None, scale=None):
    """``attention_impl`` 'auto' takes causal attention on a TPU through
    the flash kernel (forward and backward), and elsewhere materialises the
    logits up to 2048 keys and scans kv chunks past that; 'xla', 'chunked'
    and 'pallas' pick one path outright."""
    causal = cfg.causal if causal is None else causal
    window = cfg.window if window is None else window
    impl = cfg.attention_impl
    if impl == "auto":
        if causal and not kops._interpret():
            impl = "pallas"
        else:
            impl = "chunked" if q.shape[1] > 2048 else "xla"
    if impl == "pallas":
        return kops.attention(q, k, v, causal=causal, window=window, scale=scale, impl="pallas")
    if impl == "chunked":
        return attention_chunked(
            q, k, v, causal=causal, window=window, chunk=cfg.attn_chunk, scale=scale
        )
    return attention_full(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, scale=None):
    """Single-token attention against a cache.

    q: (B, 1, Hq, D); caches: (B, Smax, Hkv, D); pos: scalar index of the
    current token (keys at indices <= pos are valid).
    """
    B, _, Hq, D = q.shape
    Smax = k_cache.shape[1]
    group = Hq // k_cache.shape[2]
    kf = _repeat_kv(k_cache, group).astype(jnp.float32)
    vf = _repeat_kv(v_cache, group).astype(jnp.float32)
    scale = D**-0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kf)
    cols = jnp.arange(Smax)[None, None, None, :]
    mask = cols <= pos
    if window is not None:
        mask &= cols > pos - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)


def paged_attention(q, k_pages, v_pages, page_table, q_positions, *, window=None):
    """Chunked-query attention against a paged KV cache (mixed-step path).

    q: (B, C, Hq, D) — the chunk's queries; query ``c`` of slot ``b`` sits at
    absolute position ``q_positions[b, c]``.  k_pages/v_pages:
    (num_pages + 1, page_size, Hkv, D) pools whose page 0 is the reserved
    null page; page_table: (B, pages_per_slot) int32.  The table is LINEAR
    (page ``t // page_size`` holds absolute positions ``t``), so the
    gathered view puts absolute position ``j`` at cache column ``j`` and the
    causal mask is simply ``col <= q_position`` (± ``window``).

    Unlike the flash kernel (static ``q_offset``, uniform per-batch
    alignment) this handles PER-SLOT positions — which is exactly what a
    mixed prefill+decode step needs; the pure-prefill (all ``pos == 0``)
    chunks go through ``attention`` instead, where the kernel applies.
    """
    B, C, Hq, D = q.shape
    pages_per_slot = page_table.shape[1]
    page_size = k_pages.shape[1]
    S_max = pages_per_slot * page_size
    k = k_pages[page_table].reshape(B, S_max, -1, D)
    v = v_pages[page_table].reshape(B, S_max, -1, D)
    group = Hq // k.shape[2]
    kf = _repeat_kv(k, group).astype(jnp.float32)
    vf = _repeat_kv(v, group).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * D**-0.5, kf)
    cols = jnp.arange(S_max)[None, None, None, :]
    qpos = q_positions[:, None, :, None]
    mask = cols <= qpos
    if window is not None:
        mask &= cols > qpos - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (projections + rope + qk-norm)
# ---------------------------------------------------------------------------

def init_attn(cfg: ModelConfig, key, layers: Optional[int] = None):
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    L = (layers,) if layers else ()
    p = {
        "wq": dense_init(ks[0], L + (d, cfg.n_heads * hd)),
        "wk": dense_init(ks[1], L + (d, cfg.n_kv_heads * hd)),
        "wv": dense_init(ks[2], L + (d, cfg.n_kv_heads * hd)),
        "wo": dense_init(ks[3], L + (cfg.n_heads * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(L + (cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros(L + (cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros(L + (cfg.n_kv_heads * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros(L + (hd,), jnp.float32)
        p["k_norm"] = jnp.zeros(L + (hd,), jnp.float32)
    return p


def qkv_project(cfg: ModelConfig, p, x, positions):
    """x: (B, S, d) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with rope + qk-norm."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(cfg: ModelConfig, p, o):
    B, S = o.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"].astype(o.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None, layers: Optional[int] = None):
    """SwiGLU MLPs carry SEPARATE ``w_gate`` / ``w_up`` projections (not a
    fused ``wi``): under tensor parallelism both are column-parallel on d_ff,
    and a fused (d, 2*d_ff) matrix would interleave gate and up columns
    across model shards under the ``(None, 'model')`` rule.  Old fused
    checkpoints are migrated on restore (``train.checkpoint``)."""
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    L = (layers,) if layers else ()
    k1, k2 = jax.random.split(key)
    if cfg.act == "swiglu":
        kg, ku = jax.random.split(k1)
        return {
            "w_gate": dense_init(kg, L + (d, d_ff)),
            "w_up": dense_init(ku, L + (d, d_ff)),
            "wo": dense_init(k2, L + (d_ff, d)),
        }
    return {
        "wi": dense_init(k1, L + (d, d_ff)),
        "wo": dense_init(k2, L + (d_ff, d)),
    }


def mlp(cfg: ModelConfig, p, x):
    dt = x.dtype
    if cfg.act == "swiglu":
        gate = x @ p["w_gate"].astype(dt)
        up = x @ p["w_up"].astype(dt)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    else:
        h = x @ p["wi"].astype(dt)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    return h @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def masked_mean(values, mask=None):
    """Mean of ``values`` over the positions ``mask`` marks (all of them when
    ``mask`` is None).  THE loss-reduction tail, shared by ``softmax_xent``
    and the vocab-parallel cross-entropy (``models.tp.vocab_parallel_xent``)
    so the two cannot disagree on masked-CE semantics."""
    if mask is None:
        return jnp.mean(values)
    maskf = mask.astype(jnp.float32)
    return jnp.sum(values * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)


def softmax_xent(logits, labels, mask=None):
    """Mean cross-entropy in fp32. logits (…, V), labels (…) int32.

    The label log-prob is extracted with an iota-select reduction instead of
    take_along_axis: a gather over a vocab-sharded logits tensor would force
    GSPMD to all-gather the full logits; select+reduce stays sharded."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    return masked_mean(lse - ll, mask)


def next_token_loss(logits, tokens):
    """Shifted LM loss: predict tokens[:, 1:] from logits[:, :-1]."""
    return softmax_xent(logits[:, :-1], tokens[:, 1:])
