"""Pallas TPU kernel: fused SGD-Nesterov inner update (Table C.1).

Elementwise, memory-bound: reads (x, h, g), writes (x', h') in one HBM pass,
fusing weight decay + momentum + Nesterov look-ahead + the parameter step.
x and g come in their own dtypes (as the parameters and gradients are) and
are cast to fp32 in VMEM; h is fp32.  x' and h' are written over x and h.
The wrapper (``kernels/ops.py``) picks the (rows, cols) view and the block
from the leaf's shape.
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
    block_cols: int | None = None,
    interpret: bool = False,
):
    """Fused update on (rows, cols) arrays in (block_rows, block_cols)
    blocks (``block_cols`` None: the whole width).  x and g may be any float
    dtype; h is fp32.  x' (x's dtype) and h' are written over x and h."""
    rows, cols = x.shape
    block_cols = block_cols or cols
    assert rows % block_rows == 0 and cols % block_cols == 0, (
        x.shape, block_rows, block_cols)
    lr2d = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    grid = (rows // block_rows, cols // block_cols)
    blk = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, momentum=momentum, weight_decay=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x.dtype),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        ],
        # x' and h' overwrite x and h: inside the inner loop they are the
        # loop's carry, which XLA would otherwise copy out before each call
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
        name="fused_nesterov",
    )(lr2d, x, h, g)
