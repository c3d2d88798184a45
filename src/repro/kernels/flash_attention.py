"""Pallas TPU kernels: block-wise (flash) attention with GQA, its own
backward, causal and sliding-window masks.

The kernels see head-major (B, H, S, D) arrays, so every block is a
(block x D) tile whose two minor dims are the sequence and the head dim
(Mosaic tiles the last two dims of a block; a (1, block, 1, D) block over
(B, S, H, D) is refused).  q and k share a head dim D; v has its own, Dv.
Operands enter the MXU in the dtype they arrive in (bf16 in training,
float32 in the CPU tests) with float32 results; the online-softmax
statistics and every accumulator are float32.  ``scale`` multiplies q
once, before the kernels, in float32.

* Forward — grid (B, Hq, q blocks, kv blocks), kv innermost and sequential:
  the running max, sum and output live in VMEM scratch across kv blocks.
  Under autodiff it also writes each row's log-sum-exp, the one residual
  beside the output.
* dq — the same grid; recomputes each score tile from q, k and the
  log-sum-exp and accumulates dq = dS K.
* dk, dv — grid (B, Hkv, kv blocks, group x q blocks): for one kv block it
  walks every q block of every q head of that kv head's group, so GQA's
  sum over the group happens in the accumulator.  Its tiles are transposed
  (kv rows on sublanes, q on lanes), so the log-sum-exp and the row sums
  of dO.O broadcast along sublanes and no tile is transposed.

GQA sits in the index maps (``h // group``).  Masks apply at block
granularity first: a tile wholly above the diagonal or wholly outside the
window is neither computed nor fetched, since its index map is clamped
onto the nearest live block and the pipeline re-uses the tile it holds.
Only the tiles that straddle a mask edge (or the padding) build the
element mask.  Masked scores are -1e30, not -inf, so no NaN arises.
Block sizes come from the sequence lengths (``_block``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # the statistics' scratch is (block_q, LANES), one value a row
# the longest block side: (512 x 512) f32 score tiles.  On a TPU v5e it was
# within 2% of the best of eight block shapes from 128 to 1024 at both
# training cells' attention, and 3.6-4.2x faster than 128
MAX_BLOCK = 512
NT = (((1,), (1,)), ((), ()))  # a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b


def _block(n: int) -> int:
    """A block side for a sequence of n: the whole of it up to
    ``MAX_BLOCK``, else the largest power of two from there down to 128
    that divides n, else 128 (the sequence is padded to a multiple)."""
    if n <= MAX_BLOCK:
        return n
    b = MAX_BLOCK
    while b > LANES and n % b:
        b //= 2
    return b


class _Plan(NamedTuple):
    """What the kernels need to know besides the arrays (static)."""

    causal: bool
    window: int | None
    scale: float
    q_offset: int  # kv position of q row 0 (aligns the sequence ends)
    kv_valid: int  # kv columns at and past this are padding
    block_q: int
    block_k: int
    group: int  # q heads per kv head
    interpret: bool


def _live(p: _Plan, i, j):
    """Whether q block i sees any column of kv block j."""
    q_lo = p.q_offset + i * p.block_q
    k_lo = j * p.block_k
    live = True
    if p.causal:
        live = k_lo <= q_lo + p.block_q - 1
    if p.window is not None:
        live = jnp.logical_and(live, k_lo + p.block_k - 1 > q_lo - p.window)
    return live


def _unmasked(p: _Plan, i, j):
    """Whether every row of q block i sees every column of kv block j."""
    q_lo = p.q_offset + i * p.block_q
    k_lo = j * p.block_k
    full = k_lo + p.block_k <= p.kv_valid
    if p.causal:
        full = jnp.logical_and(full, k_lo + p.block_k - 1 <= q_lo)
    if p.window is not None:
        full = jnp.logical_and(full, k_lo > q_lo + p.block_q - 1 - p.window)
    return full


def _kv_span(p: _Plan, i, nk: int):
    """The first and last kv block that q block i may see."""
    hi = nk - 1
    if p.causal:
        hi = jnp.minimum(hi, lax.div(p.q_offset + (i + 1) * p.block_q - 1, p.block_k))
    lo = 0
    if p.window is not None:
        lo = lax.div(jnp.maximum(p.q_offset + i * p.block_q - p.window + 1, 0), p.block_k)
    return lo, hi


def _q_span(p: _Plan, j, nq: int):
    """The first and last q block that may see kv block j."""
    lo = 0
    if p.causal:
        lo = lax.div(jnp.maximum(j * p.block_k - p.q_offset, 0), p.block_q)
    hi = nq - 1
    if p.window is not None:
        last = j * p.block_k + p.block_k + p.window - 2 - p.q_offset
        hi = jnp.minimum(hi, lax.div(jnp.maximum(last, 0), p.block_q))
    return jnp.minimum(lo, hi), hi


def _mask(p: _Plan, i, j, shape, transposed=False):
    """The element mask of tile (q block i, kv block j); ``transposed``:
    the tile is (kv, q)."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    rows = p.q_offset + i * p.block_q + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = j * p.block_k + lax.broadcasted_iota(jnp.int32, shape, k_axis)
    mask = cols < p.kv_valid
    if p.causal:
        mask = jnp.logical_and(mask, cols <= rows)
    if p.window is not None:
        mask = jnp.logical_and(mask, cols > rows - p.window)
    return mask


def _on_live_tiles(p: _Plan, i, j, body):
    """Run ``body(masked)`` on a live tile, without the element mask where
    the whole tile is visible."""
    live, full = _live(p, i, j), _unmasked(p, i, j)
    pl.when(jnp.logical_and(live, full))(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(lambda: body(True))


def _lanes(x, n: int):
    """A (rows, LANES) column of equal lanes, widened to n lanes."""
    return jnp.tile(x, (1, n // LANES)) if n % LANES == 0 else x[:, :1]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, p: _Plan, nk: int):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        v = v_ref[...]
        s = lax.dot_general(q_ref[...], k_ref[...], NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(p, i, j, s.shape), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - _lanes(m_new, s.shape[1]))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(e, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = lax.dot_general(e.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, pv.shape[1]) + pv

    _on_live_tiles(p, i, j, body)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / _lanes(l, o_ref.shape[1])).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = (m_scr[...] + jnp.log(l))[:, 0][None, :]


@functools.partial(jax.jit, static_argnames=("p", "save_lse"))
def _forward(q, k, v, p: _Plan, save_lse: bool):
    """q: (B, Hq, Sq, D), already scaled; k: (B, Hkv, Skv, D); v: (B, Hkv,
    Skv, Dv), both sequences padded to their blocks.  Returns o (B, Hq,
    Sq, Dv) and, with ``save_lse``, the log-sum-exp (B, Hq, 1, Sq)."""
    B, Hq, Sq, D = q.shape
    Dv = v.shape[-1]
    bq, bk = p.block_q, p.block_k
    nq, nk = Sq // bq, k.shape[2] // bk

    def kv_map(b, h, i, j):
        lo, hi = _kv_span(p, i, nk)
        return b, h // p.group, jnp.clip(j, lo, hi), 0

    o_spec = pl.BlockSpec((None, None, bq, Dv), lambda b, h, i, j: (b, h, i, 0))
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, nk=nk),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D), kv_map),
            pl.BlockSpec((None, None, bk, Dv), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=p.interpret,
        name="flash_attention",
    )(q, k, v)
    return out if save_lse else (out[0], None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_scr,
               *, p: _Plan, nk: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(p, i, j, s.shape), s, NEG_INF)
        e = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], NT, preferred_element_type=jnp.float32)
        ds = e * (dp - di_ref[0][:, None])
        acc_scr[...] += lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32
        )

    _on_live_tiles(p, i, j, body)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = (acc_scr[...] * p.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, p: _Plan, nq: int):
    j, t = pl.program_id(2), pl.program_id(3)
    i = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[...], do_ref[...]
        s = lax.dot_general(k_ref[...], q, NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(p, i, j, s.shape, transposed=True), s, NEG_INF)
        e = jnp.exp(s - lse_ref[...])  # (kv, q) tile; the rows broadcast
        dv_scr[...] += lax.dot_general(
            e.astype(do.dtype), do, NN, preferred_element_type=jnp.float32
        )
        dp = lax.dot_general(v_ref[...], do, NT, preferred_element_type=jnp.float32)
        ds = e * (dp - di_ref[...])
        dk_scr[...] += lax.dot_general(
            ds.astype(q.dtype), q, NN, preferred_element_type=jnp.float32
        )

    _on_live_tiles(p, i, j, body)

    @pl.when(t == p.group * nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p",))
def _dq(q, k, v, do, lse, di, p: _Plan):
    """The gradient of the unscaled q, from the scaled one."""
    B, Hq, Sq, D = q.shape
    Dv = v.shape[-1]
    bq, bk = p.block_q, p.block_k
    nq, nk = Sq // bq, k.shape[2] // bk

    def kv_map(b, h, i, j):
        lo, hi = _kv_span(p, i, nk)
        return b, h // p.group, jnp.clip(j, lo, hi), 0

    def q_row(b, h, i, j):
        return b, h, i, 0

    return pl.pallas_call(
        functools.partial(_dq_kernel, p=p, nk=nk),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), q_row),
            pl.BlockSpec((None, None, bk, D), kv_map),
            pl.BlockSpec((None, None, bk, Dv), kv_map),
            pl.BlockSpec((None, None, bq, Dv), q_row),
            pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D), q_row),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=p.interpret,
        name="flash_attention",
    )(q, k, v, do, lse, di)


@functools.partial(jax.jit, static_argnames=("p",))
def _dkv(q, k, v, do, lse, di, p: _Plan):
    """The gradients of k and v, each summed over its group of q heads."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, Dv = v.shape
    bq, bk, g = p.block_q, p.block_k, p.group
    nq, nk = Sq // bq, Skv // bk

    def q_block(b, h, j, t):
        lo, hi = _q_span(p, j, nq)
        return b, h * g + t // nq, jnp.clip(t % nq, lo, hi)

    def q_map(b, h, j, t):
        return (*q_block(b, h, j, t), 0)

    def row_map(b, h, j, t):
        b, hq, i = q_block(b, h, j, t)
        return b, hq, 0, i

    def kv_own(b, h, j, t):
        return b, h, j, 0

    return pl.pallas_call(
        functools.partial(_dkv_kernel, p=p, nq=nq),
        grid=(B, Hkv, nk, g * nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), q_map),
            pl.BlockSpec((None, None, bk, D), kv_own),
            pl.BlockSpec((None, None, bk, Dv), kv_own),
            pl.BlockSpec((None, None, bq, Dv), q_map),
            pl.BlockSpec((None, None, 1, bq), row_map),
            pl.BlockSpec((None, None, 1, bq), row_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, D), kv_own),
            pl.BlockSpec((None, None, bk, Dv), kv_own),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32), pltpu.VMEM((bk, Dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=p.interpret,
        name="flash_attention",
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# autodiff
# ---------------------------------------------------------------------------


def _scaled(q, p: _Plan):
    return (q.astype(jnp.float32) * p.scale).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, p: _Plan):
    return _forward(_scaled(q, p), k, v, p, False)[0]


def _attend_fwd(q, k, v, p: _Plan):
    qs = _scaled(q, p)
    o, lse = _forward(qs, k, v, p, True)
    return o, (qs, k, v, o, lse)


def _attend_bwd(p: _Plan, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, :, None, :]
    return _dq(q, k, v, do, lse, di, p), *_dkv(q, k, v, do, lse, di, p)


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv).
    Returns (B, Sq, Hq, Dv).  ``scale`` defaults to D**-0.5; q's rows sit
    at the end of the kv sequence (decode-style q when Sq < Skv).  Block
    sides default to ``_block`` of each sequence; differentiable."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    block_q = min(block_q or _block(Sq), max(8, Sq))
    block_k = min(block_k or _block(Skv), max(8, Skv))
    plan = _Plan(
        causal=causal,
        window=window,
        scale=float(D**-0.5 if scale is None else scale),
        q_offset=Skv - Sq,
        kv_valid=Skv,
        block_q=block_q,
        block_k=block_k,
        group=Hq // Hkv,
        interpret=interpret,
    )

    def head_major(x, block):
        x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % block), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3)

    out = _attend(head_major(q, block_q), head_major(k, block_k), head_major(v, block_k), plan)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
