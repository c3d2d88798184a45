"""Sharding rules: map every parameter/state/batch leaf to a PartitionSpec.

Rules are written against *trailing* dimensions (leaves may carry leading
layer-stack axes of varying depth) and keyed by leaf name, with a
divisibility guard — a dim is only sharded over ``model`` when its size is a
multiple of the axis size, otherwise it stays replicated.  The SlowMo worker
axis (leading dim of every training-parameter leaf) is sharded over the
layout's worker mesh axes.

Sharding summary (Megatron-style within each worker):
* embed: vocab over model        * lm/cls head: vocab over model
* attn wq/wk/wv (+biases): head-out dim over model (column-parallel)
* mlp w_gate/w_up (de-fused swiglu) / gelu wi / w_in: d_ff over model (column)
* attn wo / mlp wo / w_down / w_out: contracting dim over model (row-parallel)
* MoE expert wi/wo (L, E, d, f): EXPERT dim over model (expert parallelism)
* router / norms / small gates / feature_proj: replicated
* recurrent widths (lru, conv, gates): channel dim over model

``model_spec_tail`` is THE rule; everything else here is a consumer view of
it: ``spmd_state_specs`` (specs for arrays ENTERING shard_map — all
functions in this module run outside the mapped body), ``model_shard_dims``
(feeds ``packing.make_sharded_pack_spec``) and ``model_sharded_mask`` (feeds
the leaf-aware TP clip/drift reductions).  ``slowmo_state_specs`` states the
rule field by field for a whole SlowMoState; ``tests/test_spec_rules.py``
pins that it and ``spmd_state_specs`` agree leaf-for-leaf on every preset.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import WorkerLayout

PyTree = Any

# name -> rule on trailing dims. Each entry is a tuple of axis slots applied
# to the LAST len(entry) dims; 'M' marks the dim sharded over model axes.
_TAIL_RULES_3PLUS = {  # applied when leaf ndim (sans worker) >= len + 1 stack
    # MoE expert weights (…, E, d, f): shard experts
    "wi": ("M", None, None),
    "wo": ("M", None, None),
}
_TAIL_RULES = {
    "embed": ("M", None),
    "lm_head": (None, "M"),
    "cls_head": (None, "M"),
    # feature_proj feeds the backbone directly: its OUTPUT is the replicated
    # residual stream, so column-sharding it would force an all-gather right
    # after (and breaks the manual TP loss).  It is small (~frontend_dim x
    # d_model) — replicate it on both execution paths.
    "feature_proj": (None, None),
    "wq": (None, "M"),
    "wk": (None, "M"),
    "wv": (None, "M"),
    "bq": ("M",),
    "bk": ("M",),
    "bv": ("M",),
    "wi": (None, "M"),
    "wo": ("M", None),
    "w_up": (None, "M"),
    "w_gate": (None, "M"),
    "w_in": (None, "M"),
    "w_down": ("M", None),
    "w_out": ("M", None),
    "w_gates": (None, "M"),
    "w_a": (None, "M"),
    "w_x": (None, "M"),
    "b_a": ("M",),
    "b_x": ("M",),
    "lam": ("M",),
    "conv_w": ("M",),
    "router": (None, None),  # replicated (small, fp32)
    "r_gates": (),  # replicated
}

_MOE_CONTAINERS = ("moe_blocks",)


def _leaf_name(path) -> tuple[str, tuple[str, ...]]:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(str(p.idx))
    return (keys[-1] if keys else ""), tuple(keys)


def model_spec_tail(name: str, containers: tuple[str, ...], shape, model_size: int):
    """Trailing-dim PartitionSpec entries for one model-parameter leaf.

    THE model-sharding rule of the repo: the shard_map execution path
    (``spmd_state_specs``, ``serve_param_specs``) and the tensor-parallel
    packing (``model_shard_dims`` -> ``packing.make_sharded_pack_spec``) derive
    which dim of which leaf shards over ``model`` from this one function, so
    they cannot disagree.  ``model_size <= 1`` means no tensor parallelism —
    everything replicates over (absent or size-1) model axes."""
    ndim = len(shape)
    if model_size <= 1:
        return (None,) * ndim
    in_moe = any(c in containers for c in _MOE_CONTAINERS)
    rule = None
    if in_moe and name in _TAIL_RULES_3PLUS and ndim >= 4 and name != "shared":
        # expert weights are 4D (L, E, d, f); shared-expert weights are 3D
        if "shared" not in containers:
            rule = _TAIL_RULES_3PLUS[name]
    if rule is None:
        rule = _TAIL_RULES.get(name)
    if rule is None or len(rule) > ndim:
        return (None,) * ndim
    tail = []
    for slot, dim in zip(rule, shape[ndim - len(rule):]):
        if slot == "M" and dim % model_size == 0 and dim >= model_size:
            tail.append("model")
        else:
            tail.append(None)
    return (None,) * (ndim - len(rule)) + tuple(tail)


def _specs_for_tree(tree_shapes: PyTree, model_size: int, prefix: tuple = ()) -> PyTree:
    def one(path, leaf):
        name, keys = _leaf_name(path)
        shape = leaf.shape
        if len(shape) < len(prefix):
            return P()
        tail = model_spec_tail(name, keys[:-1], shape[len(prefix):], model_size)
        return P(*(prefix + tail))

    return jax.tree_util.tree_map_with_path(one, tree_shapes)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _msize(layout: WorkerLayout) -> int:
    # single source of truth for the effective TP degree: launch.mesh
    return layout.model_shard


def _mentry(layout: WorkerLayout):
    """Model axes as a collective/PartitionSpec entry (None if TP-free)."""
    present = tuple(
        a for a in layout.model_axes if a in layout.mesh.axis_names
    )
    if not present or _msize(layout) <= 1:
        return None
    return present if len(present) > 1 else present[0]


def model_shard_dims(tree_shapes: PyTree, model_size: int) -> PyTree:
    """Per-leaf index of the model-sharded dimension (None = replicated),
    from the SAME ``model_spec_tail`` rules as both sharding paths — the
    input ``packing.make_sharded_pack_spec`` needs to build the per-model-
    shard flat-buffer layout of a TP state."""

    def one(path, leaf):
        name, keys = _leaf_name(path)
        tail = model_spec_tail(name, keys[:-1], leaf.shape, model_size)
        for i, slot in enumerate(tail):
            if slot == "model":
                return i
        return None

    return jax.tree_util.tree_map_with_path(one, tree_shapes)


def model_sharded_mask(tree_shapes: PyTree, model_size: int) -> PyTree:
    """Bool-per-leaf mirror of ``tree_shapes``: True where the SAME
    ``model_spec_tail`` rules shard the leaf over ``model``.  This is the
    leaf-awareness input of the TP global-norm clip and drift metric
    (``base_opt.make_grad_sq_fn``): sharded leaves' contributions psum over
    ``model``, replicated leaves count once.  Leaves may carry extra leading
    axes (the SlowMo worker axis) — rules match trailing dims."""

    def one(path, leaf):
        name, keys = _leaf_name(path)
        tail = model_spec_tail(name, keys[:-1], leaf.shape, model_size)
        return "model" in tail

    return jax.tree_util.tree_map_with_path(one, tree_shapes)


def _wax_entry(layout: WorkerLayout):
    if not layout.worker_axes:
        return (None,)
    return (layout.worker_axes if len(layout.worker_axes) > 1 else layout.worker_axes[0],)


def slowmo_state_specs(layout: WorkerLayout, state_shapes) -> PyTree:
    """PartitionSpec tree for a SlowMoState (shapes from jax.eval_shape),
    written out field by field from ``model_spec_tail``: the independent
    statement of the rule that ``spmd_state_specs`` must agree with leaf for
    leaf (only tests reach it)."""
    M = _msize(layout)
    wax = _wax_entry(layout)

    params_specs = _specs_for_tree(state_shapes.params, M, prefix=wax)
    inner_h = _specs_for_tree(state_shapes.inner.h, M, prefix=wax)
    inner_v = jax.tree.map(
        lambda s, spec: spec if s.ndim > 0 else P(),
        state_shapes.inner.v,
        _specs_for_tree(state_shapes.inner.v, M, prefix=wax),
    )

    # outer state: worker axis only present for the noaverage variant
    outer_leaf = jax.tree.leaves(state_shapes.outer_params)
    param_leaf = jax.tree.leaves(state_shapes.params)
    noavg = outer_leaf[0].ndim == param_leaf[0].ndim
    outer_specs = _specs_for_tree(
        state_shapes.outer_params, M, prefix=wax if noavg else ()
    )

    from ..core.slowmo import SlowMoState
    from ..core.base_opt import InnerOptState
    from ..core.gossip import GossipState

    gossip_w_spec = P(*wax) if state_shapes.gossip.w.ndim else P()
    stale_leaves = jax.tree.leaves(state_shapes.gossip.stale)
    stale_specs = (
        _specs_for_tree(state_shapes.gossip.stale, M, prefix=wax)
        if stale_leaves and stale_leaves[0].ndim > 0
        else jax.tree.map(lambda _: P(), state_shapes.gossip.stale)
    )
    return SlowMoState(
        params=params_specs,
        inner=InnerOptState(h=inner_h, v=inner_v, count=P()),
        gossip=GossipState(
            w=gossip_w_spec,
            stale=stale_specs,
            stale_w=P() if state_shapes.gossip.stale_w.ndim == 0 else gossip_w_spec,
        ),
        outer_params=outer_specs,
        slow_u=outer_specs,
        step=P(),
        outer_step=P(),
        # overlap_boundary double buffers: snapshot like params, anchor
        # like the (replicated) outer iterate, mask over the worker axes
        boundary=(
            _specs_for_tree(state_shapes.boundary, M, prefix=wax)
            if state_shapes.boundary is not None
            else None
        ),
        stale_outer=(
            outer_specs if state_shapes.stale_outer is not None else None
        ),
        boundary_mask=(
            P(*wax) if state_shapes.boundary_mask is not None else None
        ),
        # compression residual: per-worker like params (error feedback is
        # local to the worker that accumulated it)
        residual=(
            _specs_for_tree(state_shapes.residual, M, prefix=wax)
            if state_shapes.residual is not None
            else None
        ),
    )


def spmd_state_specs(layout: WorkerLayout, state, *, exact_average: bool) -> PyTree:
    """PartitionSpec tree for a SlowMoState entering ``shard_map``.

    Every leaf carrying a leading worker axis is sharded over the layout's
    worker mesh axes; scalars and (for ``exact_average``) the replicated
    outer iterate / slow momentum get ``P()`` over the worker axes.  ``state``
    may be concrete arrays or ``jax.eval_shape`` structs — only structure,
    ndim and (for TP) trailing shapes are read.

    Tensor-parallel layouts (model axes of size > 1) additionally shard the
    trailing dims of every parameter-shaped leaf via the SAME
    ``model_spec_tail`` rules as ``slowmo_state_specs`` (one rule): params / momentum / gossip messages get ``P(wax, *model_tail)``,
    and the "replicated" outer iterate / slow momentum are replicated over
    the worker axes only — over ``model`` they stay sharded, so the outer
    update runs on the local shard.

    Packed flat-buffer states (``repro.core.packing``): a ``(W, rows, 1024)``
    buffer is one leaf whose leading axis is the worker axis; under TP the
    state must be packed with the shard-major ``ShardedPackSpec``, whose row
    dimension shards over the model axes — each device then holds exactly
    its local model shard of every buffer.
    """
    from ..core.base_opt import InnerOptState
    from ..core.gossip import GossipState
    from ..core.slowmo import SlowMoState
    from ..core import packing

    wentry = _wax_entry(layout)[0]
    mentry = _mentry(layout)
    M = _msize(layout)
    packed = packing.is_packed(state.params)

    def wspec(leaf):
        return P(wentry) if getattr(leaf, "ndim", 0) else P()

    if mentry is None:
        def wtree(tree):
            return jax.tree.map(wspec, tree)

        def rep(tree):
            return jax.tree.map(lambda _: P(), tree)
    elif packed:
        # shard-major flat buffers: rows (dim -2) shard over model
        def wtree(tree):
            return jax.tree.map(
                lambda leaf: P(wentry, mentry)
                if getattr(leaf, "ndim", 0) >= 2
                else wspec(leaf),
                tree,
            )

        def rep(tree):
            return jax.tree.map(
                lambda leaf: P(mentry) if getattr(leaf, "ndim", 0) >= 2 else P(),
                tree,
            )
    else:
        # per-leaf tree layout: trailing dims via model_spec_tail, leading
        # worker axis over the worker mesh axes
        def wtree(tree):
            return _specs_for_tree(tree, M, prefix=(wentry,))

        def rep(tree):
            return _specs_for_tree(tree, M, prefix=())

    outer = rep if exact_average else wtree
    return SlowMoState(
        params=wtree(state.params),
        inner=InnerOptState(
            h=wtree(state.inner.h), v=wtree(state.inner.v), count=P()
        ),
        gossip=GossipState(
            w=wspec(state.gossip.w),
            stale=wtree(state.gossip.stale),
            stale_w=wspec(state.gossip.stale_w),
        ),
        outer_params=outer(state.outer_params),
        slow_u=outer(state.slow_u),
        step=P(),
        outer_step=P(),
        # overlap_boundary double buffers (None — an empty subtree — when
        # off): the in-flight snapshot shards like params, its anchor
        # replicates like the outer iterate (overlap requires
        # exact_average), and the riding mask shards like the mask input
        boundary=wtree(state.boundary),
        stale_outer=rep(state.stale_outer),
        boundary_mask=(
            None if state.boundary_mask is None else P(wentry)
        ),
        # compression residual: worker-leading like params — each device
        # keeps its local workers' error feedback
        residual=wtree(state.residual),
    )


def _bax_entry(layout: WorkerLayout):
    bax = layout.batch_axes
    if not bax:
        return None
    return bax if len(bax) > 1 else bax[0]


def batch_partition_spec(layout: WorkerLayout, ndim: int) -> P:
    """THE batch-leaf rule for a ``(tau, W, B, ...)`` training-batch leaf:
    dim 1 (the worker axis) shards over the layout's worker mesh axes, dim 2
    (each worker's batch) over its batch axes — on the hierarchical layout
    that is ``P(None, 'pod', 'data')``.

    The shard_map mesh path (``spmd_batch_specs``) and the NamedSharding
    view (``batch_shardings``) wrap this one function, so they cannot
    disagree on which axes shard the batch.  Pinned by
    ``tests/test_hierarchical_spmd.py``.
    """
    entries = [None, _wax_entry(layout)[0]]
    if layout.batch_axes and ndim >= 3:
        entries.append(_bax_entry(layout))
    return P(*entries)


def spmd_batch_specs(layout: WorkerLayout, batches: PyTree) -> PyTree:
    """PartitionSpecs of training batches entering ``shard_map``."""
    return jax.tree.map(
        lambda x: batch_partition_spec(layout, getattr(x, "ndim", 0)), batches
    )


def spmd_mask_spec(layout: WorkerLayout) -> P:
    """PartitionSpec of the ``(W,)`` per-round participation mask entering
    ``shard_map`` (masked exact average): sharded over the worker mesh axes
    like every worker-leading state leaf, so the mapped body sees its local
    workers' slice."""
    return P(_wax_entry(layout)[0])


def batch_shardings(layout: WorkerLayout, batch_shapes: PyTree) -> PyTree:
    """NamedShardings of training batches, from ``batch_partition_spec``."""
    mesh = layout.mesh
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_partition_spec(layout, leaf.ndim)),
        batch_shapes,
    )


def serve_param_specs(layout: WorkerLayout, param_shapes: PyTree) -> PyTree:
    """Raw PartitionSpec tree of serving parameters ENTERING ``shard_map``
    (the continuous-batching TP serve step): no worker axis, trailing dims
    model-sharded by the SAME ``model_spec_tail`` rules as training — the
    shard layout the `--tp M` engine serves is the one checkpoints train."""
    return _specs_for_tree(param_shapes, _msize(layout), prefix=())


def serve_pool_spec(layout: WorkerLayout, pool_shape: tuple) -> P:
    """PartitionSpec of one paged-KV page pool ``(L, num_pages + 1,
    page_size, Hkv, hd)`` entering ``shard_map``: the kv-head dim shards
    over the model axes (each shard's column-parallel ``wk``/``wv`` produce
    exactly its local heads), everything else — pages, offsets — is
    replicated bookkeeping."""
    mentry = _mentry(layout)
    M = _msize(layout)
    spec = [None] * len(pool_shape)
    hkv = pool_shape[-2]
    if mentry is not None and hkv % M == 0 and hkv >= M:
        spec[-2] = mentry
    return P(*spec)
