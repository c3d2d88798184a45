"""Flat-buffer packing: one contiguous (rows, 1024) buffer per dtype group.

SlowMo's boundary cost is per-*leaf* everywhere the state is a pytree: one
``pallas_call`` per parameter leaf in ``kernels/ops.py`` and one
all-reduce / collective-permute per leaf on the mesh backend.  Packing the
state once at init into a few dtype-homogeneous ``(rows, LANES)`` buffers
with a *static* leaf-offset index turns the outer boundary into ONE kernel
launch and ONE collective, and the tree layout is recovered only where it
is semantically needed (the ``loss_fn`` boundary and checkpoints).

Design:

* ``PackSpec`` — static, hashable metadata: the source treedef, per-leaf
  ``LeafSlot``s (shape / dtype / flat offset / group), and per-group row
  counts.  Rows are rounded up to a multiple of ``ROW_ALIGN`` so every
  packed buffer tiles cleanly into Pallas blocks with no re-padding.
* ``Packed`` — a registered pytree container holding ``{group: buffer}``.
  Because it is a pytree, ALL the tree-generic algorithm code in
  ``slowmo.py`` / ``base_opt.py`` / ``gossip.py`` / ``comm.py`` runs on
  packed state unchanged — with ~one leaf instead of hundreds.
* Leaves may carry extra *leading* axes (the SlowMo worker axis): a tree of
  ``(W,) + shape`` leaves packs to ``(W, rows, LANES)`` buffers, so the
  worker mean over a packed buffer is a single ``lax.pmean``.

Group keys are the dtype names of the tree the spec was built from (the
*layout* label); the storage dtype of any individual packed tree may be
overridden (e.g. fp32 momentum buffers sharing the layout of bf16 params).
Pad regions are written as zeros and every update in this repo maps zeros
to zeros, so they stay zero for the lifetime of the state.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

LANES = 1024  # matches kernels/ops.py tiling
# Rows per buffer are padded to this multiple so the kernel dispatcher
# (kernels/ops.py::_tiling) always finds an exactly-dividing block size
# >= 64 and takes the copy-free path; the cost is < 64*LANES
# elements of tail padding per buffer (256 KiB fp32) — noise for real models.
ROW_ALIGN = 64


@jax.tree_util.register_pytree_node_class
class Packed:
    """Dict of dtype-homogeneous flat buffers, as a registered pytree."""

    __slots__ = ("buffers",)

    def __init__(self, buffers: dict):
        self.buffers = dict(buffers)

    def tree_flatten(self):
        keys = tuple(sorted(self.buffers))
        return tuple(self.buffers[k] for k in keys), keys

    @classmethod
    def tree_unflatten(cls, keys, children):
        return cls(dict(zip(keys, children)))

    def __getitem__(self, key):
        return self.buffers[key]

    def __iter__(self):
        return iter(sorted(self.buffers))

    def __len__(self):
        return len(self.buffers)

    def __repr__(self):
        items = ", ".join(
            f"{k}: {getattr(v, 'shape', v)}" for k, v in sorted(self.buffers.items())
        )
        return f"Packed({items})"


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside its group's flat buffer."""

    key: str  # jax keystr of the leaf path (leaf_view lookup / debugging)
    shape: tuple[int, ...]
    dtype: str  # dtype of the spec-build tree (layout label)
    group: str  # buffer key this leaf is packed into
    offset: int  # element offset into the group's flat buffer
    size: int


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static description of a pytree -> flat-buffer packing (hashable)."""

    treedef: Any
    slots: tuple[LeafSlot, ...]
    group_rows: tuple[tuple[str, int], ...]  # (group, rows) in packing order

    @property
    def groups(self) -> tuple[str, ...]:
        return tuple(g for g, _ in self.group_rows)

    def rows(self, group: str) -> int:
        return dict(self.group_rows)[group]

    @property
    def num_elements(self) -> int:
        """Total PACKED elements (padding included), all groups."""
        return sum(r * LANES for _, r in self.group_rows)

    # -- packing ------------------------------------------------------------

    def _lead(self, leaves) -> tuple[int, ...]:
        """Leading (e.g. worker) axes shared by every leaf; validated."""
        lead = tuple(leaves[0].shape[: leaves[0].ndim - len(self.slots[0].shape)])
        for slot, leaf in zip(self.slots, leaves):
            if tuple(leaf.shape) != lead + slot.shape:
                raise ValueError(
                    f"leaf {slot.key}: shape {tuple(leaf.shape)} != "
                    f"lead {lead} + spec {slot.shape}"
                )
        return lead

    @jax.named_scope("layout")
    def pack(self, tree: PyTree, dtype=None) -> Packed:
        """Pack ``tree`` into flat buffers shaped ``lead + (rows, LANES)``.

        ``dtype`` overrides the storage dtype of every group (e.g. pack
        fp32 gradients into the layout of bf16 parameters); default is each
        group's own dtype.  The tail (and inter-leaf) pad region is
        zero-filled.  Implementation note: leaves are written into a zeros
        buffer with ``dynamic_update_slice`` rather than concatenated —
        XLA:CPU lowers a wide concatenate ~3x slower than the equivalent
        slice updates, and this is on the per-step gradient path.
        """
        leaves, td = jax.tree.flatten(tree)
        if td != self.treedef:
            raise ValueError(f"tree structure mismatch:\n got {td}\n want {self.treedef}")
        lead = self._lead(leaves)
        buffers = {}
        for group, rows in self.group_rows:
            store = jnp.dtype(dtype) if dtype is not None else jnp.dtype(group)
            buf = jnp.zeros(lead + (rows * LANES,), store)
            for slot, leaf in zip(self.slots, leaves):
                if slot.group != group:
                    continue
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf,
                    leaf.astype(store).reshape(lead + (-1,)),
                    slot.offset,
                    axis=len(lead),
                )
            buffers[group] = buf.reshape(lead + (rows, LANES))
        return Packed(buffers)

    @jax.named_scope("layout")
    def unpack(self, packed: Packed, dtype=None) -> PyTree:
        """Recover the pytree; leaves keep the buffer's storage dtype unless
        ``dtype`` is given.  Slices + reshapes only — no arithmetic."""
        leaves = []
        for slot in self.slots:
            leaf = _slot_view(packed[slot.group], slot)
            leaves.append(leaf.astype(dtype) if dtype is not None else leaf)
        return jax.tree.unflatten(self.treedef, leaves)

    def leaf_view(self, packed: Packed, key: str) -> jax.Array:
        """One leaf (by keystr or unique suffix) out of the packed buffers."""
        matches = [s for s in self.slots if s.key == key or s.key.endswith(key)]
        if len(matches) != 1:
            raise KeyError(f"{key!r} matches {len(matches)} leaves")
        slot = matches[0]
        return _slot_view(packed[slot.group], slot)

    def zeros(self, lead: tuple[int, ...] = (), dtype=None) -> Packed:
        """Packed zeros with the same layout (momentum-buffer init)."""
        return Packed(
            {
                g: jnp.zeros(tuple(lead) + (rows, LANES), dtype or jnp.dtype(g))
                for g, rows in self.group_rows
            }
        )

    def scalars(self, dtype=jnp.float32) -> Packed:
        """Per-group scalar zeros: the zero-cost placeholder layout (SGD's
        unused second-moment slot, gossip's unused stale messages)."""
        return Packed({g: jnp.zeros((), dtype) for g, _ in self.group_rows})


def _slot_view(buf: jax.Array, slot: LeafSlot) -> jax.Array:
    """The leaf ``slot`` out of a ``lead + (rows, LANES)`` buffer: the rows
    it spans, flattened, then its elements.  Flattening only those rows keeps
    any relayout the flattening needs to the leaf's own size (a whole
    buffer's would hold a second copy of it)."""
    lead = tuple(buf.shape[:-2])
    r0 = slot.offset // LANES
    r1 = -(-(slot.offset + slot.size) // LANES)
    rows = jax.lax.slice_in_dim(buf, r0, r1, axis=len(lead)).reshape(lead + (-1,))
    start = slot.offset - r0 * LANES
    return jax.lax.slice_in_dim(rows, start, start + slot.size, axis=len(lead)).reshape(
        lead + slot.shape
    )


def make_pack_spec(tree: PyTree) -> PackSpec:
    """Build the static packing index for ``tree`` (concrete arrays or
    ``jax.eval_shape`` structs).  Leaves are grouped by dtype, concatenated
    in flatten order, and each group's row count is padded to ``ROW_ALIGN``
    so packed buffers always tile into Pallas blocks without copies."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if not flat:
        raise ValueError("cannot pack an empty pytree")
    offsets: dict[str, int] = {}
    slots = []
    for path, leaf in flat:
        group = jnp.dtype(leaf.dtype).name
        off = offsets.get(group, 0)
        size = 1
        for d in leaf.shape:
            size *= int(d)
        slots.append(
            LeafSlot(
                key=jax.tree_util.keystr(path),
                shape=tuple(int(d) for d in leaf.shape),
                dtype=group,
                group=group,
                offset=off,
                size=size,
            )
        )
        offsets[group] = off + size
    group_rows = tuple(
        (g, _round_up(_round_up(total, LANES) // LANES, ROW_ALIGN))
        for g, total in offsets.items()
    )
    return PackSpec(treedef=treedef, slots=tuple(slots), group_rows=group_rows)


@dataclasses.dataclass(frozen=True)
class ShardedPackSpec:
    """Tensor-parallel packing: shard-major flat buffers over model shards.

    The GLOBAL layout of every group buffer is ``num_shards`` consecutive row
    blocks, block ``s`` holding shard ``s`` of every model-sharded leaf (its
    slice along ``shard_dims``) plus a full copy of every replicated leaf.
    Sharding the row dimension of that buffer over the mesh's model axes
    therefore hands each device exactly its local model shard, laid out by
    the plain per-shard ``PackSpec`` in ``.shard`` — which is what the mapped
    round body (``repro.distributed.spmd``) uses for its pack/unpack
    boundaries, its fused-Nesterov kernel launches (rows stay ROW_ALIGN-
    aligned per shard) and its boundary all-reduce, whose bytes shrink by
    1/num_shards relative to the unsharded packing.

    This object speaks the same interface as ``PackSpec`` (pack / unpack /
    zeros / scalars / rows / groups), but with GLOBAL semantics — ``pack``
    takes the full parameter tree, ``unpack`` returns it — so ``init_slowmo``,
    checkpoints and the trainer use it as a drop-in ``pack``.  Calling
    contract: the GLOBAL methods here run OUTSIDE the mapped round only
    (init / checkpoint / eval boundaries); INSIDE the shard_map body every
    device carries one shard block and all pack/unpack goes through the
    plain per-shard spec in ``.shard`` (``distributed.spmd`` passes exactly
    that to ``make_slowmo_round``).

    Caveat: replicated leaves appear once per shard block, so a reduction
    taken blindly over a global buffer (e.g. a global gradient norm) would
    count them ``num_shards`` times.  Leaf-aware reductions (``clip_norm``,
    ``track_drift``) therefore split each buffer with ``sharded_ranges()`` —
    psum the sharded slices over ``model``, count the replicated remainder
    once (see ``base_opt.make_grad_sq_fn``).
    """

    shard: PackSpec  # layout of ONE model shard (the mapped body's spec)
    shard_dims: tuple  # per-slot model-sharded dim index (None = replicated)
    full_shapes: tuple  # per-slot FULL (unsharded) leaf shape
    num_shards: int

    @staticmethod
    def _gather(x):
        """Replicate a committed device-sharded array before host-side
        slicing.  Sliced eagerly, every slice that crosses the shard
        boundaries of a committed input is its own SPMD program with
        collectives; on jax 0.9.0's XLA:CPU forced-host devices several
        such programs in flight at once can starve the in-process
        collective rendezvous until it aborts the process.  One gather per
        buffer makes every later slice local.  These global<->tree
        conversions only run at init/checkpoint/eval boundaries — never in
        the mapped round body — so the gather is off the hot path.  Tracers
        and uncommitted arrays pass through."""
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, jax.sharding.NamedSharding) and not sh.is_fully_replicated:
                return jax.device_put(
                    x,
                    jax.sharding.NamedSharding(
                        sh.mesh, jax.sharding.PartitionSpec()
                    ),
                )
        return x

    @property
    def treedef(self):
        return self.shard.treedef

    @property
    def groups(self) -> tuple[str, ...]:
        return self.shard.groups

    def rows(self, group: str) -> int:
        return self.num_shards * self.shard.rows(group)

    @property
    def group_rows(self) -> tuple[tuple[str, int], ...]:
        return tuple((g, self.num_shards * r) for g, r in self.shard.group_rows)

    @property
    def num_elements(self) -> int:
        return self.num_shards * self.shard.num_elements

    def _shard_tree(self, tree: PyTree, s: int) -> PyTree:
        """Shard ``s`` of a full tree (leaves may carry extra leading axes)."""
        leaves, td = jax.tree.flatten(tree)
        if td != self.shard.treedef:
            raise ValueError(
                f"tree structure mismatch:\n got {td}\n want {self.shard.treedef}"
            )
        out = []
        for leaf, dim, fshape in zip(leaves, self.shard_dims, self.full_shapes):
            lead = leaf.ndim - len(fshape)
            if tuple(leaf.shape[lead:]) != tuple(fshape):
                raise ValueError(
                    f"leaf shape {tuple(leaf.shape)} does not end in the "
                    f"spec's full shape {tuple(fshape)}"
                )
            if dim is None:
                out.append(leaf)
            else:
                k = fshape[dim] // self.num_shards
                out.append(
                    jax.lax.slice_in_dim(leaf, s * k, (s + 1) * k, axis=lead + dim)
                )
        return jax.tree.unflatten(self.shard.treedef, out)

    @jax.named_scope("layout")
    def pack(self, tree: PyTree, dtype=None) -> Packed:
        """Full tree -> global shard-major buffers ``lead + (S*rows, LANES)``."""
        # gather committed sharded leaves ONCE, not once per shard block
        tree = jax.tree.map(self._gather, tree)
        blocks = [
            self.shard.pack(self._shard_tree(tree, s), dtype=dtype)
            for s in range(self.num_shards)
        ]
        some = next(iter(blocks[0].buffers.values()))
        lead_ndim = some.ndim - 2
        return Packed(
            {
                g: jnp.concatenate([b[g] for b in blocks], axis=lead_ndim)
                for g in self.shard.groups
            }
        )

    @jax.named_scope("layout")
    def unpack(self, packed: Packed, dtype=None) -> PyTree:
        """Global shard-major buffers -> the full tree (concat over shards)."""
        packed = Packed({g: self._gather(v) for g, v in packed.buffers.items()})
        some = next(iter(packed.buffers.values()))
        lead_ndim = some.ndim - 2
        block_leaves = []
        for s in range(self.num_shards):
            blk = Packed(
                {
                    g: jax.lax.slice_in_dim(
                        packed[g], s * r, (s + 1) * r, axis=lead_ndim
                    )
                    for g, r in self.shard.group_rows
                }
            )
            block_leaves.append(jax.tree.leaves(self.shard.unpack(blk, dtype=dtype)))
        leaves = []
        for i, dim in enumerate(self.shard_dims):
            if dim is None:
                leaves.append(block_leaves[0][i])
            else:
                lead = block_leaves[0][i].ndim - len(self.full_shapes[i])
                leaves.append(
                    jnp.concatenate(
                        [bl[i] for bl in block_leaves], axis=lead + dim
                    )
                )
        return jax.tree.unflatten(self.shard.treedef, leaves)

    def zeros(self, lead: tuple[int, ...] = (), dtype=None) -> Packed:
        return Packed(
            {
                g: jnp.zeros(
                    tuple(lead) + (self.num_shards * rows, LANES),
                    dtype or jnp.dtype(g),
                )
                for g, rows in self.shard.group_rows
            }
        )

    def scalars(self, dtype=jnp.float32) -> Packed:
        return self.shard.scalars(dtype)

    # -- leaf-aware reductions (TP clip_norm / track_drift) -----------------
    def sharded_ranges(self) -> "ShardRanges":
        """Per-GROUP static ``(offset, size)`` element ranges of the
        model-SHARDED slots in the per-shard buffer layout, adjacent ranges
        coalesced.

        One shard block holds shard ``s`` of every sharded leaf next to a
        full copy of every replicated leaf, so a cross-shard reduction over
        the local buffer must treat the two regions differently.  Ranges
        (slices of the flattened buffer) make that split without
        materializing a buffer-sized mask constant — the consumer
        (``base_opt.make_grad_sq_fn``) sums the sharded slices and derives
        the replicated remainder as ``total - sharded``."""
        out = []
        for g, _ in self.shard.group_rows:
            ranges: list[list[int]] = []
            for slot, dim in zip(self.shard.slots, self.shard_dims):
                if slot.group != g or dim is None:
                    continue
                if ranges and ranges[-1][0] + ranges[-1][1] == slot.offset:
                    ranges[-1][1] += slot.size
                else:
                    ranges.append([slot.offset, slot.size])
            out.append((g, tuple((o, s) for o, s in ranges)))
        return ShardRanges(by_group=tuple(out))

    def tree_sharded_mask(self) -> PyTree:
        """Bool-per-leaf mirror of the packed tree (True = model-sharded) —
        the per-leaf-layout counterpart of ``sharded_ranges`` for round
        phases that carry the unpacked tree (the local base's tree-carry
        inner loop)."""
        return jax.tree.unflatten(
            self.shard.treedef, [d is not None for d in self.shard_dims]
        )


@dataclasses.dataclass(frozen=True)
class ShardRanges:
    """Static ``group -> ((offset, size), ...)`` index of the model-sharded
    elements inside a per-shard packed buffer (``ShardedPackSpec.
    sharded_ranges``).  A dedicated type — not a plain dict — so consumers
    (``base_opt.make_grad_sq_fn``) can distinguish it from a dict-structured
    per-leaf bool mask; hashable, so round builders can close over it."""

    by_group: tuple  # ((group, ((offset, size), ...)), ...)

    def get(self, group: str, default=()):
        return dict(self.by_group).get(group, default)


def make_sharded_pack_spec(tree: PyTree, shard_dims: PyTree, num_shards: int) -> ShardedPackSpec:
    """Build the shard-major packing index for ``tree`` split ``num_shards``
    ways.  ``shard_dims`` mirrors ``tree`` with, per leaf, the index of its
    model-sharded dimension or ``None`` for replicated leaves (the caller —
    ``sharding.model_shard_dims`` — derives it from the SAME ``model_spec_tail``
    rules both execution paths trust)."""
    if num_shards < 2:
        raise ValueError("ShardedPackSpec needs num_shards >= 2; use make_pack_spec")
    leaves, treedef = jax.tree.flatten(tree)
    dims, dims_def = jax.tree.flatten(
        shard_dims, is_leaf=lambda x: x is None or isinstance(x, int)
    )
    if dims_def != treedef:
        raise ValueError("shard_dims tree does not mirror the packed tree")
    shard_leaves = []
    full_shapes = []
    for leaf, dim in zip(leaves, dims):
        shape = tuple(int(d) for d in leaf.shape)
        full_shapes.append(shape)
        if dim is None:
            shard_leaves.append(leaf)
            continue
        if shape[dim] % num_shards:
            raise ValueError(
                f"leaf {shape} dim {dim} not divisible by {num_shards} shards"
            )
        sshape = shape[:dim] + (shape[dim] // num_shards,) + shape[dim + 1:]
        shard_leaves.append(jax.ShapeDtypeStruct(sshape, leaf.dtype))
    shard = make_pack_spec(jax.tree.unflatten(treedef, shard_leaves))
    return ShardedPackSpec(
        shard=shard,
        shard_dims=tuple(dims),
        full_shapes=tuple(full_shapes),
        num_shards=num_shards,
    )


def is_packed(tree: PyTree) -> bool:
    return isinstance(tree, Packed)


# ---------------------------------------------------------------------------
# SlowMoState <-> packed-state conversion (checkpoint interchange)
# ---------------------------------------------------------------------------

def _unpack_or_scalars(spec: PackSpec, leaf_like: PyTree, packed) -> PyTree:
    """Packed buffer -> tree; Packed scalars -> the tree-of-scalars layout."""
    vals = list(packed.buffers.values())
    if vals and vals[0].ndim == 0:
        return jax.tree.map(lambda _: jnp.zeros((), jnp.float32), leaf_like)
    return spec.unpack(packed)


def unpack_state(spec: PackSpec, state):
    """Packed SlowMoState -> the tree-layout state ``init_slowmo`` builds,
    so checkpoints written from packed runs are interchangeable with (and
    validated against) the per-leaf layout."""
    params = spec.unpack(state.params)
    return state._replace(
        params=params,
        inner=state.inner._replace(
            h=spec.unpack(state.inner.h),
            v=_unpack_or_scalars(spec, params, state.inner.v),
        ),
        gossip=state.gossip._replace(
            stale=_unpack_or_scalars(spec, params, state.gossip.stale),
        ),
        outer_params=spec.unpack(state.outer_params),
        slow_u=spec.unpack(state.slow_u),
        boundary=(
            spec.unpack(state.boundary) if state.boundary is not None else None
        ),
        stale_outer=(
            spec.unpack(state.stale_outer)
            if state.stale_outer is not None
            else None
        ),
        residual=(
            spec.unpack(state.residual) if state.residual is not None else None
        ),
    )


def _pack_or_scalars(spec: PackSpec, tree: PyTree) -> Packed:
    leaves = jax.tree.leaves(tree)
    if leaves and all(getattr(x, "ndim", 0) == 0 for x in leaves):
        return spec.scalars()
    return spec.pack(tree, dtype=jnp.float32)


def pack_state(spec: PackSpec, state):
    """Tree-layout SlowMoState -> packed state (checkpoint restore path)."""
    return state._replace(
        params=spec.pack(state.params),
        inner=state.inner._replace(
            h=spec.pack(state.inner.h, dtype=jnp.float32),
            v=_pack_or_scalars(spec, state.inner.v),
        ),
        gossip=state.gossip._replace(
            stale=_pack_or_scalars(spec, state.gossip.stale),
        ),
        outer_params=spec.pack(state.outer_params, dtype=jnp.float32),
        slow_u=spec.pack(state.slow_u, dtype=jnp.float32),
        boundary=(
            spec.pack(state.boundary) if state.boundary is not None else None
        ),
        stale_outer=(
            spec.pack(state.stale_outer, dtype=jnp.float32)
            if state.stale_outer is not None
            else None
        ),
        residual=(
            spec.pack(state.residual, dtype=jnp.float32)
            if state.residual is not None
            else None
        ),
    )
