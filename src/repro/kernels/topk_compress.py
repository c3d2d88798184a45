"""Per-block magnitude top-k for boundary compression.

DeMo-style sparsification of the SlowMo boundary signal (PAPERS.md,
arXiv 2411.19870 / 2510.03371): each worker transmits only the k
largest-magnitude entries of its boundary delta (plus the error-feedback
residual), as a statically-shaped (values, indices) payload, and the
untransmitted remainder is carried forward locally.

The payload layout is deterministic and shared by the selection and the
collective contract (``analysis/contract.py``):

* a signal of n elements splits into fixed blocks via ``payload_spec`` —
  ``BLOCK_ELEMS``-sized blocks when n is a multiple of ``BLOCK_ELEMS``
  (the packed (rows, 1024) flat buffers always are: rows are 64-aligned),
  else one block covering the whole leaf (tree layout);
* per block, ``k = max(1, floor(ratio * block_elems))`` entries survive.
  FLOOR, deliberately: at ratio 0.1 the (f32 value + s32 index) payload is
  ``6553 * 8 / 262144 ≈ 0.19999x`` the dense f32 bytes — under the 0.2x
  budget that ``ceil`` would overshoot.  At ratio 1.0, k = block_elems and
  reconstruction is exact (the dense-equivalence case).

Per-block k keeps every payload statically shaped, so the all-gather that
replaces the dense boundary all-reduce (``comm.worker_mean_sparse``) has a
fixed HLO census the contract can budget.

The selection is XLA's ``jax.lax.top_k`` on every backend: Mosaic (the
Pallas TPU compiler) has no lowering for ``top_k``, so there is no kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 1024
BLOCK_ROWS = 64
BLOCK_ELEMS = BLOCK_ROWS * LANES  # 65536 elements per top-k block


def payload_spec(n: int, ratio: float) -> tuple[int, int, int]:
    """Static payload shape for an n-element signal at ``ratio``.

    Returns ``(num_blocks, block_elems, k)``: the signal reshapes to
    ``(num_blocks, block_elems)`` and each block keeps its top k entries
    by magnitude.  Pure layout arithmetic — no tracing.
    """
    if n <= 0:
        raise ValueError(f"empty signal (n={n})")
    if not (0.0 < ratio <= 1.0):
        raise ValueError(f"compress ratio must be in (0, 1], got {ratio}")
    if n >= BLOCK_ELEMS and n % BLOCK_ELEMS == 0:
        blocks, be = n // BLOCK_ELEMS, BLOCK_ELEMS
    else:
        blocks, be = 1, n
    k = max(1, min(be, int(ratio * be)))
    return blocks, be, k


def sparsify_ref(flat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Magnitude top-k of a (..., block_elems) signal; pure-jnp oracle.

    Returns ``(values, indices)`` of shape (..., k) — f32 signed values and
    s32 positions within each block (``jax.lax.top_k`` breaks ties by
    lowest index).
    """
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    vals = jnp.take_along_axis(flat, idx, axis=-1).astype(jnp.float32)
    return vals, idx


def reconstruct(vals: jax.Array, idx: jax.Array, block_elems: int) -> jax.Array:
    """Scatter a (..., k) payload back to a dense (..., block_elems) f32
    array; untransmitted positions are zero.  Indices within a block are
    unique (top-k), so set-scatter is well-defined."""

    def one(v, i):
        return jnp.zeros((block_elems,), jnp.float32).at[i].set(
            v.astype(jnp.float32)
        )

    fn = one
    for _ in range(vals.ndim - 1):
        fn = jax.vmap(fn)
    return fn(vals, idx)


def sparsify_batch(
    x: jax.Array, ratio: float
) -> tuple[jax.Array, jax.Array, tuple[int, int, int]]:
    """Per-slot magnitude top-k of a batched signal.

    ``x`` is (L, ...) — one independent signal per leading slot (the local
    worker axis).  Returns ``(values, indices, spec)`` with payloads of
    shape (L, num_blocks, k) and ``spec = payload_spec(per-slot n, ratio)``.
    """
    L = x.shape[0]
    n = x.size // L
    spec = payload_spec(n, ratio)
    blocks, be, k = spec
    flat = x.reshape(L, n).astype(jnp.float32)
    vals, idx = sparsify_ref(flat.reshape(L * blocks, be), k)
    return vals.reshape(L, blocks, k), idx.reshape(L, blocks, k), spec
