"""Pallas TPU kernel: fused SGD-Nesterov inner update (Table C.1).

Elementwise, memory-bound: reads (x, h, g), writes (x', h') in one HBM pass,
fusing weight decay + momentum + Nesterov look-ahead + the parameter step.
Same (rows, 1024) tiling strategy as slowmo_update.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024
DEFAULT_BLOCK_ROWS = 256


def _kernel(lr_ref, x_ref, h_ref, g_ref, x_out_ref, h_out_ref, *, momentum, weight_decay):
    lr = lr_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * x
    h_new = momentum * h_ref[...] + g
    d = momentum * h_new + g
    h_out_ref[...] = h_new
    x_out_ref[...] = (x - lr * d).astype(x_out_ref.dtype)


def fused_nesterov_2d(
    x: jax.Array,
    h: jax.Array,
    g: jax.Array,
    lr: jax.Array,
    *,
    momentum: float,
    weight_decay: float = 0.0,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
):
    """Fused update on (rows, LANES) arrays; h is fp32, x/g any float dtype."""
    rows, lanes = x.shape
    assert lanes == LANES and rows % block_rows == 0, (x.shape, block_rows)
    lr2d = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    grid = (rows // block_rows,)
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, momentum=momentum, weight_decay=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), x.dtype),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="fused_nesterov",
    )(lr2d, x, h, g)
