"""Jit'd dispatch wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

All entry points operate on parameter *pytrees* (the kernels themselves
operate on padded 2D tiles).  Two regimes:

* tree layout — leaves are flattened, padded to (rows, 1024) and dispatched
  leaf-by-leaf (one ``pallas_call`` + a pad copy per leaf);
* packed layout (``repro.core.packing``) — leaves ARE ``(..., rows, 1024)``
  buffers with rows a multiple of the block size, so ``_to_2d`` is a free
  reshape and the whole state runs as a single ``pallas_call`` per buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import fused_nesterov as _fn
from . import ref
from . import slowmo_update as _su

LANES = _su.LANES


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@jax.named_scope("layout")
def _to_2d(x: jax.Array, block_rows: int):
    """Flatten + zero-pad to (rows, LANES) with rows % block_rows == 0.

    Aligned inputs (packed flat buffers: trailing dim LANES and a row count
    divisible by ``block_rows``) take the no-copy path — a pure reshape."""
    if x.ndim >= 2 and x.shape[-1] == LANES and (x.size // LANES) % block_rows == 0:
        return x.reshape(-1, LANES), x.size
    flat = x.reshape(-1)
    n = flat.shape[0]
    per_block = block_rows * LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANES), n


@jax.named_scope("layout")
def _from_2d(y2d: jax.Array, n: int, shape) -> jax.Array:
    if y2d.size == n:
        return y2d.reshape(shape)
    return y2d.reshape(-1)[:n].reshape(shape)


def _pick_block_rows(x: jax.Array) -> int:
    """Block size chosen from the PADDED row count with bounded waste.

    Prefer a block size that divides the rows exactly (packed buffers are
    64-row aligned, so they always tile copy-free); otherwise take the
    largest block whose round-up padding stays under max(7 rows, 12.5%) of
    the leaf — big leaves keep big blocks (small relative pad) while
    sub-tile leaves no longer pad to a full 256-row tile."""
    rows = max(1, -(-x.size // LANES))
    for br in (256, 64):
        if rows % br == 0:
            return br
    for br in (256, 64, 8):
        if -rows % br <= max(7, rows // 8):
            return br
    return 1


# ---------------------------------------------------------------------------
# SlowMo outer update (Algorithm 1 lines 7-8), over pytrees
# ---------------------------------------------------------------------------

def slowmo_outer_update(x0, x_tau, u, *, gamma, alpha, beta, use_pallas=False):
    """Fused u/x0 update on pytrees. Returns (x0_new, u_new)."""
    gamma = jnp.asarray(gamma, jnp.float32)
    if not use_pallas:
        pairs = jax.tree.map(
            lambda a, b, c: ref.slowmo_outer_update_ref(
                a, b, c, gamma=gamma, alpha=alpha, beta=beta
            ),
            x0,
            x_tau,
            u,
        )
        x_new = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
        u_new = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
        return x_new, u_new

    interpret = _interpret()

    def one(a, b, c):
        br = _pick_block_rows(a)
        a2, n = _to_2d(a.astype(jnp.float32), br)
        b2, _ = _to_2d(b.astype(jnp.float32), br)
        c2, _ = _to_2d(c.astype(jnp.float32), br)
        xo, uo = _su.slowmo_update_2d(
            a2, b2, c2, gamma, alpha=alpha, beta=beta, block_rows=br,
            interpret=interpret,
        )
        return _from_2d(xo, n, a.shape), _from_2d(uo, n, a.shape)

    pairs = jax.tree.map(one, x0, x_tau, u)
    x_new = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    u_new = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return x_new, u_new


# ---------------------------------------------------------------------------
# Fused Nesterov inner step, over pytrees
# ---------------------------------------------------------------------------

def fused_nesterov_update(x, h, g, *, lr, momentum, weight_decay=0.0, use_pallas=False):
    """Fused x/h update on pytrees. Returns (x_new, h_new)."""
    lr = jnp.asarray(lr, jnp.float32)
    if not use_pallas:
        pairs = jax.tree.map(
            lambda a, b, c: ref.fused_nesterov_ref(
                a, b, c, lr=lr, momentum=momentum, weight_decay=weight_decay
            ),
            x,
            h,
            g,
        )
    else:
        interpret = _interpret()

        def one(a, b, c):
            br = _pick_block_rows(a)
            a2, n = _to_2d(a, br)
            b2, _ = _to_2d(b.astype(jnp.float32), br)
            # keep gradients in fp32 (the kernel accumulates in fp32 anyway);
            # casting them down to bf16 params would lose precision vs. ref
            c2, _ = _to_2d(c.astype(jnp.float32), br)
            xo, ho = _fn.fused_nesterov_2d(
                a2, b2, c2, lr, momentum=momentum, weight_decay=weight_decay,
                block_rows=br, interpret=interpret,
            )
            return _from_2d(xo, n, a.shape), _from_2d(ho, n, a.shape)

        pairs = jax.tree.map(one, x, h, g)
    x_new = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    h_new = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return x_new, h_new


# ---------------------------------------------------------------------------
# Attention dispatch
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, scale=None, window=None, impl="xla"):
    """GQA attention: impl='xla' (einsum oracle) or 'pallas' (flash kernel)."""
    if impl == "pallas":
        from . import flash_attention as _fa

        return _fa.flash_attention(
            q, k, v, causal=causal, scale=scale, window=window,
            interpret=_interpret(),
        )
    return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale, window=window)
