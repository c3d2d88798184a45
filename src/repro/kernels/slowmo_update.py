"""Pallas TPU kernel: fused SlowMo outer update (Algorithm 1, lines 7-8).

The outer update is purely elementwise over three N-sized fp32 arrays
(x_{t,0}, x_{t,tau}, u) producing two outputs.  Unfused, XLA emits separate
subtract / scale / axpy passes; the fused kernel reads each operand from HBM
exactly once and writes each output once — the op is memory-bound, so this
halves HBM traffic for the outer boundary (which for large N dominates the
SlowMo overhead on-chip).

Layout: the wrapper (``kernels/ops.py``) views each leaf as (rows, cols)
and picks (block_rows, block_cols) fp32 blocks of about 256K elements from
its shape — packed buffers are (rows, 1024) in (256 or 64, 1024) blocks;
3 inputs + 2 outputs = 5 * 256K * 4B = 5 MiB, 10 MiB double-buffered, under
the 16 MiB of VMEM.
gamma (the fast LR, traced) is staged through SMEM as a (1,1) scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024
DEFAULT_BLOCK_ROWS = 256


def _kernel(gamma_ref, x0_ref, xtau_ref, u_ref, x_out_ref, u_out_ref, *, alpha, beta):
    gamma = gamma_ref[0, 0]
    x0 = x0_ref[...]
    delta = (x0 - xtau_ref[...]) * (1.0 / gamma)
    u_new = beta * u_ref[...] + delta
    u_out_ref[...] = u_new
    x_out_ref[...] = x0 - (alpha * gamma) * u_new


def slowmo_update_2d(
    x0: jax.Array,
    x_tau: jax.Array,
    u: jax.Array,
    gamma: jax.Array,
    *,
    alpha: float,
    beta: float,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int | None = None,
    interpret: bool = False,
):
    """Fused update on (rows, cols) fp32 arrays in (block_rows, block_cols)
    blocks (``block_cols`` None: the whole width). Returns (x_new, u_new)."""
    rows, cols = x0.shape
    block_cols = block_cols or cols
    assert rows % block_rows == 0 and cols % block_cols == 0, (
        x0.shape, block_rows, block_cols)
    gamma2d = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    grid = (rows // block_rows, cols // block_cols)
    blk = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # gamma scalar
            blk,
            blk,
            blk,
        ],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        ],
        # x0 -> x_new and u -> u_new in place: a donated state is updated
        # where it lies, with no copy of either kept live for the round
        input_output_aliases={1: 0, 3: 1},
        interpret=interpret,
        name="slowmo_update",
    )(gamma2d, x0, x_tau, u)
