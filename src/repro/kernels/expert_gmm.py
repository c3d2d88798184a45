"""Grouped matrix products over the experts a layer holds (Pallas TPU).

Rows arrive sorted by expert, each expert's rows padded to whole tiles of
``tm`` rows, so every row tile belongs to one group: ``tile_group[i]`` is
the group of tile i, and only the first ``n_tiles`` tiles are computed (a
dynamic grid, after the design of ``jax.experimental.pallas.ops.tpu.
megablox``).  Rows past the active tiles are neither read nor written: the
output there is undefined, and callers read only active rows.

* ``gmm(lhs (M, K), rhs (G, K, N))``: each tile's rows times its group's
  matrix; ``transpose_rhs`` multiplies by ``rhs[g].T`` (``rhs`` (G, N, K)).
* ``tgmm(lhs (M, K), dout (M, N))``: per group, its rows of ``lhs``
  transposed times its rows of ``dout``, (G, K, N).  Every group owns at
  least one tile, so every group's block is written.
* ``expert_gmm``: ``gmm`` with a ``custom_vjp`` whose backward is one
  ``gmm`` (transposed) and one ``tgmm``.

Every ``pallas_call`` here bears the name ``expert_gmm``: forward, both
backward products and the remat recompute show in a device trace as
custom calls ``%expert_gmm.N``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "expert_gmm"
_VMEM_LIMIT = 64 * 1024 * 1024
_MAX_BLOCK = 3 * 1024 * 1024  # elements of a (tk, tn) weight block


def _div_block(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where none is (a full-width block)."""
    fits = [b for b in range(128, min(dim, cap) + 1, 128) if dim % b == 0]
    return max(fits) if fits else dim


def _blocks(k: int, n: int) -> tuple[int, int]:
    """(tk, tn): the reduction block as wide as 2048 allows, then the
    output block as wide as the weight block's budget allows."""
    tk = _div_block(k, 2048)
    return tk, _div_block(n, max(128, _MAX_BLOCK // tk))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


@functools.partial(jax.jit, static_argnames=("tm", "transpose_rhs", "interpret"))
def gmm(lhs, rhs, tile_group, n_tiles, *, tm: int, transpose_rhs: bool = False,
        interpret: bool = False):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    assert m % tm == 0, (m, tm)
    tk, tn = _blocks(k, n)
    nk = k // tk
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(tg_ref, nt_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims, preferred_element_type=jnp.float32
        )

        @pl.when(kk == nk - 1)
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, tk), lambda j, i, kk, tg, nt: (tg[i], j, kk))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn), lambda j, i, kk, tg, nt: (tg[i], kk, j))
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, i, kk, tg, nt: (i, kk)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, tg, nt: (i, j)),
            grid=(n // tn, n_tiles, nk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=NAME,
    )
    return call(tile_group, n_tiles[None], lhs, rhs.astype(lhs.dtype))


@functools.partial(jax.jit, static_argnames=("groups", "tm", "interpret"))
def tgmm(lhs, dout, tile_group, n_tiles, *, groups: int, tm: int,
         interpret: bool = False):
    m, k = lhs.shape
    n = dout.shape[1]
    tk, tn = _blocks(k, n)

    def kernel(tg_ref, nt_ref, lhs_ref, dout_ref, out_ref, acc_ref):
        i = pl.program_id(2)
        g = tg_ref[i]
        first = jnp.logical_or(i == 0, tg_ref[jnp.maximum(i - 1, 0)] != g)
        last = jnp.logical_or(i == nt_ref[0] - 1, tg_ref[i + 1] != g)

        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # the (tm, tk) block turned (tk, tm) in f32, as the TPU transposes it
        lt = lhs_ref[...].astype(jnp.float32).swapaxes(0, 1).astype(lhs_ref.dtype)
        acc_ref[...] += lax.dot(lt, dout_ref[...], preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kk, j, i, tg, nt: (i, kk)),
                pl.BlockSpec((tm, tn), lambda kk, j, i, tg, nt: (i, j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda kk, j, i, tg, nt: (tg[i], kk, j)),
            grid=(k // tk, n // tn, n_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=NAME,
    )
    # one more entry, so that the last tile's look at its successor stays in
    # bounds
    tg = jnp.concatenate([tile_group, tile_group[-1:]])
    return call(tg, n_tiles[None], lhs, dout.astype(lhs.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def expert_gmm(lhs, rhs, tile_group, n_tiles, tm: int, interpret: bool = False):
    """``gmm(lhs, rhs)`` over the active tiles, differentiable in ``lhs``
    and ``rhs``; the output takes ``lhs``'s dtype."""
    return gmm(lhs, rhs, tile_group, n_tiles, tm=tm, interpret=interpret)


def _fwd(lhs, rhs, tile_group, n_tiles, tm, interpret):
    out = gmm(lhs, rhs, tile_group, n_tiles, tm=tm, interpret=interpret)
    return out, (lhs, rhs, tile_group, n_tiles)


def _bwd(tm, interpret, res, g):
    lhs, rhs, tile_group, n_tiles = res
    dlhs = gmm(g, rhs, tile_group, n_tiles, tm=tm, transpose_rhs=True,
               interpret=interpret)
    drhs = tgmm(lhs, g, tile_group, n_tiles, groups=rhs.shape[0], tm=tm,
                interpret=interpret)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None, None


expert_gmm.defvjp(_fwd, _bwd)
