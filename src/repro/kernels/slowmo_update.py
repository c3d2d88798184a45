"""Pallas TPU kernel: fused SlowMo outer update (Algorithm 1, lines 7-8).

The outer update is purely elementwise over three N-sized fp32 arrays
(x_{t,0}, x_{t,tau}, u) producing two outputs.  Unfused, XLA emits separate
subtract / scale / axpy passes; the fused kernel reads each operand from HBM
exactly once and writes each output once — the op is memory-bound, so this
halves HBM traffic for the outer boundary (which for large N dominates the
SlowMo overhead on-chip).

Layout: the wrapper flattens/pads each leaf to (rows, 1024) so blocks are
(block_rows, 1024) fp32 tiles in VMEM — lane-dim 1024 = 8*128 keeps the VPU
fully utilised; 1024*4B rows fit comfortably in VMEM at block_rows<=512
(3 inputs + 2 outputs = 5 * 512 * 1024 * 4B = 10 MiB < 16 MiB VMEM).
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
    interpret: bool = False,
):
    """Fused update on (rows, LANES) fp32 arrays. Returns (x_new, u_new)."""
    rows, lanes = x0.shape
    assert lanes == LANES and rows % block_rows == 0, (x0.shape, block_rows)
    gamma2d = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    grid = (rows // block_rows,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
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
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="slowmo_update",
    )(gamma2d, x0, x_tau, u)
