"""Jit'd dispatch wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

All entry points operate on parameter *pytrees*; the kernels operate on 2D
arrays in 2D blocks.  Each leaf's view and block come from its shape alone
(``_tiling``):

* a leaf whose last dim C is a multiple of 128, with rows R = size / C that
  an (R, C) view keeps in whole tiles (the second-minor dim a multiple of
  the tile's rows: 8 for fp32, 16 for bf16; or every dim before it 1) and
  that a block height divides, runs as it is: merging the leading dims is
  a bitcast on the TPU's tiled layout, so nothing is copied before or
  after the kernel, and each operand enters in its own dtype.  Blocks are
  (block_rows, block_cols) of about 256K elements; packed buffers
  (``repro.core.packing``: C = 1024, rows a multiple of 64) keep their
  (256 or 64, 1024) blocks, one ``pallas_call`` per buffer;
* any other leaf (1-D norms, odd widths) is flattened and zero-padded to
  (rows, 1024) under the ``layout`` scope, with block rows chosen from the
  padded row count.

``tally()`` counts, while a program traces, the leaves and bytes each
kernel took on either path.
"""
from __future__ import annotations

import contextlib
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import fused_nesterov as _fn
from . import ref
from . import slowmo_update as _su

LANES = _su.LANES  # the padded path's row width
_BLOCK_ELEMS = 256 * LANES  # what a block aims at: today's (256, 1024)
_MAX_BLOCK_COLS = _BLOCK_ELEMS // 16  # 16 rows of a bf16 tile still fit


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


class Tiling(NamedTuple):
    """A leaf seen as a (rows, cols) array cut into (block_rows, block_cols)
    blocks; ``copy_free``: the view is the leaf's own layout, else the leaf
    is flattened and zero-padded to it."""

    rows: int
    cols: int
    block_rows: int
    block_cols: int
    copy_free: bool


def _tiling(shape, itemsize: int = 4) -> Tiling:
    """The view and blocks of a leaf whose narrowest operand has
    ``itemsize`` bytes."""
    return _native_tiling(shape, itemsize) or _padded_tiling(math.prod(shape))


def _native_tiling(shape, itemsize: int) -> Tiling | None:
    """The (R, C) view that merges only the leading dims, where the TPU's
    (8 * 4 / itemsize, 128) tiles stay whole under it and a block height
    divides R."""
    if len(shape) < 2 or shape[-1] % 128:
        return None
    cols = shape[-1]
    rows = math.prod(shape[:-1])
    sublanes = 8 * max(1, 4 // itemsize)
    if shape[-2] % sublanes and math.prod(shape[:-2]) > 1:
        return None  # the merge would cut tiles: a relayout, not a bitcast
    block_cols = cols
    if cols > _MAX_BLOCK_COLS:  # too wide for 16 rows: split the width
        block_cols = max(
            c for c in range(128, _MAX_BLOCK_COLS + 1, 128) if cols % c == 0
        )
    # a power of two, quartered: packed buffers keep 256, then 64 rows
    br = 1 << ((_BLOCK_ELEMS // block_cols).bit_length() - 1)
    while br >= sublanes:
        if rows % br == 0:
            return Tiling(rows, cols, br, block_cols, True)
        br //= 4
    if rows <= _BLOCK_ELEMS // block_cols:  # one block of the full height
        return Tiling(rows, cols, rows, block_cols, True)
    return None


def _padded_tiling(size: int) -> Tiling:
    """(rows, LANES) with block rows chosen from the PADDED row count with
    bounded waste: a block size that divides the rows exactly where one of
    256 and 64 does, else the largest whose round-up padding stays under
    max(7 rows, 12.5%) of the leaf — big leaves keep big blocks (small
    relative pad) while sub-tile leaves do not pad to a full 256-row tile."""
    rows = max(1, -(-size // LANES))
    br = next((b for b in (256, 64) if rows % b == 0), None) or next(
        (b for b in (256, 64, 8) if -rows % b <= max(7, rows // 8)), 1
    )
    return Tiling(-(-rows // br) * br, LANES, br, LANES, False)


def _to_2d(x: jax.Array, t: Tiling) -> jax.Array:
    if t.copy_free:
        return x.reshape(t.rows, t.cols)
    with jax.named_scope("layout"):
        flat = x.reshape(-1)
        return jnp.pad(flat, (0, t.rows * t.cols - flat.size)).reshape(t.rows, t.cols)


def _from_2d(y2d: jax.Array, t: Tiling, shape) -> jax.Array:
    if t.copy_free:
        return y2d.reshape(shape)
    with jax.named_scope("layout"):
        return y2d.reshape(-1)[: math.prod(shape)].reshape(shape)


# ---------------------------------------------------------------------------
# Engagement tally
# ---------------------------------------------------------------------------


class TilingTally:
    """Per kernel: leaves and bytes (operands read plus results written)
    traced on the copy-free path and on the padded one."""

    def __init__(self):
        self.counts: dict[str, dict[str, list[int]]] = {}

    def add(self, kernel: str, copy_free: bool, nbytes: int) -> None:
        path = "copy_free" if copy_free else "padded"
        slot = self.counts.setdefault(kernel, {"copy_free": [0, 0], "padded": [0, 0]})
        slot[path][0] += 1
        slot[path][1] += nbytes

    def summary(self) -> str:
        parts = []
        for kernel, c in sorted(self.counts.items()):
            (n_free, b_free), (n_pad, b_pad) = c["copy_free"], c["padded"]
            share = 100.0 * b_free / max(b_free + b_pad, 1)
            parts.append(
                f"{kernel}: {n_free} of {n_free + n_pad} leaves copy-free "
                f"({share:.1f}% of {b_free + b_pad} bytes)"
            )
        return "; ".join(parts) or "no fused kernel traced"


_TALLIES: list[TilingTally] = []


@contextlib.contextmanager
def tally():
    """Count the kernels' leaves by path while the body traces programs
    (a program loaded from the persistent cache still traces)."""
    t = TilingTally()
    _TALLIES.append(t)
    try:
        yield t
    finally:
        _TALLIES.remove(t)


def _tiled_call(name, kernel_2d, operands, out_dtypes, **kw):
    """``kernel_2d`` over one leaf's operands, viewed by the first one's
    shape; returns its results in that shape."""
    shape = operands[0].shape
    sizes = [jnp.dtype(d).itemsize for d in [o.dtype for o in operands] + out_dtypes]
    t = _tiling(shape, min(sizes))
    nbytes = math.prod(shape) * sum(sizes)
    for tal in _TALLIES:
        tal.add(name, t.copy_free, nbytes)
    outs = kernel_2d(
        *[_to_2d(o, t) for o in operands],
        block_rows=t.block_rows,
        block_cols=t.block_cols,
        interpret=_interpret(),
        **kw,
    )
    return tuple(_from_2d(o, t, shape) for o in outs)


def _unzip2(pairs, like):
    first = jax.tree.map(lambda _, p: p[0], like, pairs)
    second = jax.tree.map(lambda _, p: p[1], like, pairs)
    return first, second


# ---------------------------------------------------------------------------
# SlowMo outer update (Algorithm 1 lines 7-8), over pytrees
# ---------------------------------------------------------------------------

def slowmo_outer_update(x0, x_tau, u, *, gamma, alpha, beta, use_pallas=False):
    """Fused u/x0 update on pytrees. Returns (x0_new, u_new)."""
    gamma = jnp.asarray(gamma, jnp.float32)
    if not use_pallas:
        def one(a, b, c):
            return ref.slowmo_outer_update_ref(
                a, b, c, gamma=gamma, alpha=alpha, beta=beta
            )
    else:
        def one(a, b, c):
            f32 = [v.astype(jnp.float32) for v in (a, b, c)]
            return _tiled_call(
                "slowmo_update", _su.slowmo_update_2d, f32, [jnp.float32] * 2,
                gamma=gamma, alpha=alpha, beta=beta,
            )

    return _unzip2(jax.tree.map(one, x0, x_tau, u), x0)


# ---------------------------------------------------------------------------
# Fused Nesterov inner step, over pytrees
# ---------------------------------------------------------------------------

def fused_nesterov_update(x, h, g, *, lr, momentum, weight_decay=0.0, use_pallas=False):
    """Fused x/h update on pytrees. Returns (x_new, h_new).  x and g go to
    the kernel in their own dtypes (it computes in fp32)."""
    lr = jnp.asarray(lr, jnp.float32)
    if not use_pallas:
        def one(a, b, c):
            return ref.fused_nesterov_ref(
                a, b, c, lr=lr, momentum=momentum, weight_decay=weight_decay
            )
    else:
        def one(a, b, c):
            return _tiled_call(
                "fused_nesterov", _fn.fused_nesterov_2d, [a, b.astype(jnp.float32), c],
                [a.dtype, jnp.float32],
                lr=lr, momentum=momentum, weight_decay=weight_decay,
            )

    return _unzip2(jax.tree.map(one, x, h, g), x)


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
