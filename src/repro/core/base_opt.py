"""Inner ("base") optimizers for the SlowMo framework.

Implements the update directions of Table C.1 of the paper:

* SGD with Nesterov momentum:
    h_{k+1} = beta_local * h_k + g_k
    d_k     = beta_local * h_{k+1} + g_k
* Adam (with bias correction; the correction step index ``l`` follows the
  buffer strategy: ``l = k`` when buffers are reset at each outer boundary,
  ``l = t*tau + k`` when they are maintained — we simply carry the counter in
  the state and the boundary handler resets it or not).

All functions are pure and operate on parameter pytrees whose leaves carry a
leading worker axis ``W`` (the update is elementwise, so no vmap is needed).
The momentum/second-moment buffers mirror the parameter pytree (leading ``W``
included); the Adam step counter is a scalar (shared by all workers — workers
always take the same number of steps).

Gradients arrive here already worker-complete: on hierarchical (pod, data)
mesh layouts the inner step all-reduces them over the pod's batch shards
(``CommBackend.grad_mean``) BEFORE clipping/momentum, so ``_clip``'s
per-worker global norm, the momentum buffers, and the applied step are
computed on the full pod-batch gradient — every data replica of a worker
derives the identical update, keeping its state replicas bitwise in sync.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class InnerOptConfig:
    """Configuration of the base optimizer's local update rule."""

    kind: str = "sgd"  # 'sgd' | 'adam'
    # SGD options (paper: Nesterov momentum 0.9, weight decay 1e-4)
    momentum: float = 0.9
    nesterov: bool = True
    weight_decay: float = 0.0
    # Adam options (paper WMT: beta1=0.9, beta2=0.98, eps=1e-8)
    beta1: float = 0.9
    beta2: float = 0.98
    eps: float = 1e-8
    clip_norm: float = 0.0  # global-norm gradient clipping (0 = off)

    def __post_init__(self):
        if self.kind not in ("sgd", "adam"):
            raise ValueError(f"unknown inner optimizer kind: {self.kind!r}")


class InnerOptState(NamedTuple):
    """Buffers of the base optimizer (pytrees mirroring params)."""

    h: PyTree  # first moment / momentum buffer
    v: PyTree  # second moment (Adam only; zeros-like placeholder for SGD)
    count: jnp.ndarray  # scalar int32 step counter (for Adam bias correction)


def _zeros_like_f32(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def init_inner_state(cfg: InnerOptConfig, params: PyTree) -> InnerOptState:
    h = _zeros_like_f32(params)
    if cfg.kind == "adam":
        v = _zeros_like_f32(params)
    else:
        # SGD: keep an (empty-cost) placeholder so the pytree structure is
        # static across optimizer kinds.
        v = jax.tree.map(lambda x: jnp.zeros((), jnp.float32), params)
    return InnerOptState(h=h, v=v, count=jnp.zeros((), jnp.int32))


def make_grad_sq_fn(backend=None, sharded_mask=None):
    """Build ``sq_fn(tree) -> (W,)``: each worker's GLOBAL sum of squares
    over the full (cross-model-shard) vector of every leaf.

    ``sharded_mask`` says which parts of the state are model-sharded on this
    backend:

    * per-leaf tree layout — a pytree of python bools mirroring the tree
      (True = the leaf is sliced along a ``sharding.model_spec_tail`` dim);
    * packed flat-buffer layout — a ``packing.ShardRanges`` of static
      per-group element ranges (``packing.ShardedPackSpec.sharded_ranges``),
      since one shard buffer holds sharded slices AND full replicated copies
      side by side; the replicated remainder is derived as
      ``total - sharded`` so no buffer-sized mask is ever materialized.

    Sharded contributions are distinct per model shard and get psummed over
    ``model``; replicated contributions are identical on every shard and are
    counted ONCE.  Without a mask (or without model axes) this is the plain
    per-worker sum — the TP-free behavior.  Shared by the global-norm clip
    (``_clip``) and the drift metric (``slowmo.make_slowmo_round``)."""

    def leaf_sq(g):
        gf = g.astype(jnp.float32)
        return jnp.sum(jnp.square(gf), axis=tuple(range(1, gf.ndim)))

    if sharded_mask is None or getattr(backend, "model_shards", 1) <= 1:
        def sq_fn(tree):
            return sum(leaf_sq(g) for g in jax.tree.leaves(tree))

        return sq_fn

    from . import packing

    if isinstance(sharded_mask, packing.ShardRanges):  # packed buffers

        def sq_fn(tree):
            if not packing.is_packed(tree):
                raise ValueError(
                    "this sq_fn was built for packed buffers "
                    "(got a non-Packed tree)"
                )
            sharded = jnp.zeros((), jnp.float32)
            total = jnp.zeros((), jnp.float32)
            for g in tree:
                sq = jnp.square(tree[g].astype(jnp.float32))
                sq = sq.reshape(sq.shape[:-2] + (-1,))  # (lead..., rows*LANES)
                total = total + jnp.sum(sq, axis=tuple(range(1, sq.ndim)))
                for off, size in sharded_mask.get(g, ()):
                    seg = jax.lax.slice_in_dim(sq, off, off + size, axis=sq.ndim - 1)
                    sharded = sharded + jnp.sum(seg, axis=tuple(range(1, seg.ndim)))
            return backend.model_psum(sharded) + (total - sharded)

        return sq_fn

    mask_leaves = jax.tree.leaves(sharded_mask)

    def sq_fn(tree):
        g_leaves = jax.tree.leaves(tree)
        if len(g_leaves) != len(mask_leaves):
            raise ValueError(
                f"sharded_mask has {len(mask_leaves)} leaves for a tree "
                f"with {len(g_leaves)}"
            )
        sharded = jnp.zeros((), jnp.float32)
        replicated = jnp.zeros((), jnp.float32)
        for g, m in zip(g_leaves, mask_leaves):
            if m:
                sharded = sharded + leaf_sq(g)
            else:
                replicated = replicated + leaf_sq(g)
        return backend.model_psum(sharded) + replicated

    return sq_fn


def _clip(cfg: InnerOptConfig, grads: PyTree, sq_fn=None) -> PyTree:
    """Per-worker global-norm clip: norms computed over the non-worker dims
    of every leaf jointly (axis 0 is the worker axis).  On packed state the
    pad regions are zero, so they do not perturb the norm.  ``sq_fn``
    (``make_grad_sq_fn``) supplies the sum of squares; under tensor
    parallelism it spans all model shards, so every shard derives the SAME
    clip scale the TP-free worker would."""
    if not cfg.clip_norm:
        return grads
    sq = (sq_fn or make_grad_sq_fn())(grads)  # (W,)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-9))
    return jax.tree.map(
        lambda g: g * scale.reshape((-1,) + (1,) * (g.ndim - 1)), grads
    )


def update_direction(
    cfg: InnerOptConfig,
    state: InnerOptState,
    params: PyTree,
    grads: PyTree,
    sq_fn=None,
) -> tuple[PyTree, InnerOptState]:
    """Return the update direction ``d`` (Table C.1) and the new state.

    The caller applies ``x <- x - lr * d``.  Gradients and buffers are
    accumulated in fp32 regardless of the parameter dtype.  ``sq_fn``
    (``make_grad_sq_fn``) feeds the global-norm clip; required only for
    tensor-parallel backends, where the norm must span model shards.
    """
    grads = _clip(cfg, jax.tree.map(lambda g: g.astype(jnp.float32), grads), sq_fn)
    if cfg.weight_decay:
        grads = jax.tree.map(
            lambda g, p: g + cfg.weight_decay * p.astype(jnp.float32),
            grads,
            params,
        )
    if cfg.kind == "sgd":
        h_new = jax.tree.map(lambda h, g: cfg.momentum * h + g, state.h, grads)
        if cfg.nesterov:
            d = jax.tree.map(lambda h, g: cfg.momentum * h + g, h_new, grads)
        else:
            d = h_new
        return d, InnerOptState(h=h_new, v=state.v, count=state.count + 1)

    # Adam
    count = state.count + 1
    b1, b2 = cfg.beta1, cfg.beta2
    h_new = jax.tree.map(lambda h, g: b1 * h + (1.0 - b1) * g, state.h, grads)
    v_new = jax.tree.map(
        lambda v, g: b2 * v + (1.0 - b2) * jnp.square(g), state.v, grads
    )
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)
    d = jax.tree.map(
        lambda h, v: (h / c1) / (jnp.sqrt(v / c2) + cfg.eps), h_new, v_new
    )
    return d, InnerOptState(h=h_new, v=v_new, count=count)


def apply_step(
    cfg: InnerOptConfig,
    state: InnerOptState,
    params: PyTree,
    grads: PyTree,
    lr,
    *,
    z: PyTree | None = None,
    use_pallas: bool = False,
    sq_fn=None,
) -> tuple[PyTree, InnerOptState]:
    """One full base-optimizer step: ``params' = params - lr * d``.

    ``z`` (when given) is the de-biased iterate the direction is evaluated at
    (SGP/OSGP push-sum); the step is still applied to ``params``.  For plain
    Nesterov SGD evaluated at ``params`` itself, ``use_pallas`` routes the
    momentum + look-ahead + parameter step through the fused kernel — one HBM
    pass and (on packed state) a single launch — instead of separate
    h-update / d / axpy passes; unclipped gradients reach it in their own
    dtype.  Gradient clipping composes: it is applied to fp32 ``grads``
    before the kernel, with ``sq_fn`` (``make_grad_sq_fn``) supplying the
    TP-aware global norm on tensor-parallel backends.
    """
    fused = use_pallas and z is None and cfg.kind == "sgd" and cfg.nesterov
    if not fused:
        d, state = update_direction(
            cfg, state, z if z is not None else params, grads, sq_fn
        )
        new_params = jax.tree.map(
            lambda x, dd: (x.astype(jnp.float32) - lr * dd).astype(x.dtype),
            params,
            d,
        )
        return new_params, state

    from ..kernels import ops as kops  # local import: kernels are optional

    if cfg.clip_norm:  # the clip scales in fp32; unclipped, the kernel casts
        grads = _clip(cfg, jax.tree.map(lambda g: g.astype(jnp.float32), grads), sq_fn)
    x_new, h_new = kops.fused_nesterov_update(
        params,
        state.h,
        grads,
        lr=lr,
        momentum=cfg.momentum,
        weight_decay=cfg.weight_decay,
        use_pallas=True,
    )
    return x_new, InnerOptState(h=h_new, v=state.v, count=state.count + 1)


def reset_buffers(cfg: InnerOptConfig, state: InnerOptState) -> InnerOptState:
    """Buffer strategy 'reset' (App. B.4): zero all buffers and the counter."""
    return InnerOptState(
        h=_zeros_like_f32(state.h),
        v=jax.tree.map(jnp.zeros_like, state.v),
        count=jnp.zeros((), jnp.int32),
    )


def average_buffers(
    state: InnerOptState, backend=None
) -> InnerOptState:
    """Buffer strategy 'average': ALLREDUCE the buffers across workers.

    The buffers carry a leading worker axis; averaging over it is a plain
    array mean on the axis backend and an ``all-reduce`` (``lax.pmean``) on
    the mesh backend.  Scalar placeholder leaves are left untouched.
    """
    if backend is None:
        from . import comm

        wleaves = [x for x in jax.tree.leaves(state.h) if getattr(x, "ndim", 0)]
        backend = comm.AxisBackend(int(wleaves[0].shape[0]) if wleaves else 1)

    return InnerOptState(
        h=jax.tree.map(backend.mean_keepdims, state.h),
        v=jax.tree.map(backend.mean_keepdims, state.v),
        count=state.count,
    )
