"""Pallas TPU kernel: block-wise (flash) causal attention with GQA.

Grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks axis is the
innermost (sequential on TPU), so the running softmax statistics live in VMEM
scratch across kv iterations.  The kernel sees head-major (B, H, S, D)
arrays, so every block is a (block_q x D) or (block_k x D) tile whose two
minor dims are the sequence and the head dim (Mosaic tiles the last two
dims of a block; a (1, block, 1, D) block over (B, S, H, D) is refused).
BlockSpecs stream these tiles through VMEM; with the default 128x128 blocks
and D<=128 the working set is ~0.5 MiB — far under VMEM, leaving room for XLA
to overlap DMA with MXU work.  GQA is expressed in the k/v index_map
(``h // group``), so kv tiles are fetched once per kv head, not per q head
(they stay resident across the q-head grid axis when adjacent).

Masking uses -1e30 (not -inf) so fully-masked tiles contribute exp(.)=0
without NaNs.  Causal + optional sliding-window masks are applied with block
granularity short-circuits: tiles entirely above the diagonal (or entirely
outside the window) skip the MXU work via pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
STATS_LANES = 128  # TPU scratch wants a 128 minor dim


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    kv_valid: int,
    q_offset: int,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global row/col indices of this tile
    q_start = qi * block_q + q_offset  # position of q row 0 in kv coordinates
    k_start = ki * block_k

    should_run = True
    if causal:
        # skip tiles entirely above the diagonal
        should_run = k_start <= q_start + block_q - 1
    if window is not None:
        # skip tiles entirely left of every row's window
        should_run = jnp.logical_and(
            should_run, k_start + block_k - 1 > q_start - window
        )

    @pl.when(should_run)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < kv_valid
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = float(1.0 / (D**0.5))

    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, max(8, Skv))
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    # head-major for the kernel: (B, S, H, D) -> (B, H, S_padded, D)
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    nq, nk = Sq_p // block_q, Skv_p // block_k

    grid = (B, Hq, nq, nk)
    q_spec = pl.BlockSpec((None, None, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)
    )

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        kv_valid=Skv,
        q_offset=Skv - Sq,  # align sequence ends (supports decode-style q)
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
