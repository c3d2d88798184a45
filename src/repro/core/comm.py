"""Communication backends: how worker-axis collectives actually execute.

The SlowMo round is written once against a small ``CommBackend`` seam and can
run in two modes:

* ``AxisBackend`` ("axis") — the oracle: the m workers are a leading array
  axis of every leaf, and collectives are plain array ops (``jnp.mean`` over
  axis 0, ``jnp.roll`` along axis 0).  Single-program, single-device; this is
  the layout the rest of the repo (init, checkpoints, the paper-figure
  analogs) speaks.

* ``MeshBackend`` ("mesh") — the lowered path: the round body runs inside
  ``jax.shard_map`` with the worker axis sharded over one or
  more mesh axes.  The exact average becomes ``jax.lax.pmean`` (lowers to an
  ``all-reduce``), and gossip/topology rolls become ``jax.lax.ppermute``
  (lower to ``collective-permute``).  Leaves keep a leading *local* worker
  axis of size ``num_workers // num_worker_devices`` (1 in the one-worker-
  per-device layouts), so the algorithm code is identical in both modes.

Both backends implement the same primitive set; everything else in
``slowmo.py`` / ``gossip.py`` / ``base_opt.py`` is backend-agnostic.  See
``repro.distributed.spmd`` for the shard_map wrapper that pairs the
``MeshBackend`` with PartitionSpecs.

Hierarchical (pod, data) layouts add one more seam: ``grad_mean`` — the
every-inner-step gradient sync.  When the backend carries ``batch_axes``
(the mesh axes each worker's batch is sharded over), ``grad_mean`` is a
``lax.pmean`` over those axes: every device inside a pod ends each step with
the gradient of the FULL pod batch, so a pod behaves exactly like one
bigger-batch SlowMo worker while the SlowMo collectives (exact average,
gossip rolls, outer momentum) stay on the worker (``pod``) axes only.  On
the oracle (and on mesh layouts without batch axes) each worker already
consumes its whole batch locally, so ``grad_mean`` is the identity.

Tensor-parallel (pod, data, model) layouts grow that seam into a REDUCTION-
HOOK PAIR: ``grad_mean`` stays the batch-axis gradient sync, and the model-
axis hooks (``model_psum`` / ``model_pmax`` / ``model_index``) are where a
Megatron-style loss deposits its partial activation reductions — column-
parallel in, row-parallel out, ``psum`` over ``model`` (see
``repro.models.tp``).  Model-axis reductions live INSIDE the loss (the
forward/backward of the matmuls), so gradients leave the loss already
model-complete and the rest of the round — grad_mean over ``data``, the
boundary all-reduce over ``pod`` — is unchanged and operates on the local
model shard of every leaf.  On the oracle (and on TP-free mesh layouts) the
model hooks are the identity, which is what lets a TP-aware loss double as
its own equivalence oracle.

A loss that needs the model hooks cannot be a bare ``(params, batch)``
callable — it must know the backend.  The ``bind_loss`` protocol closes the
loop: any loss exposing ``bind_backend(backend)`` (e.g. ``models.tp.TPLoss``)
is bound by ``make_inner_step`` to whichever backend the round runs on;
plain callables pass through untouched.

The primitives are also LAYOUT-agnostic: they tree-map over whatever leaves
the state carries.  On the per-leaf tree layout that is one collective per
parameter leaf; on the packed flat-buffer layout (``repro.core.packing``)
the same ``worker_mean`` call sees a single ``(W, rows, 1024)`` buffer per
dtype group, so the exact average lowers to ONE all-reduce (and a gossip
roll to one collective-permute) per boundary — ``average_dtype=bf16`` then
halves the traffic of that one transfer instead of issuing N bf16 casts.

Calling contract (who may call what, where):

* ``AxisBackend`` methods run anywhere — they are plain array ops.
* ``MeshBackend`` methods lower to named-axis collectives and are valid
  ONLY inside the ``shard_map`` body that ``repro.distributed.spmd`` builds
  over a mesh carrying the backend's axis names; calling them outside a
  mapped region (or under a different mesh) is a trace-time error.
* Losses never touch worker-axis primitives — they reach ONLY the model
  hooks, and only via ``bind_loss``; the round body (``slowmo``/``gossip``/
  ``base_opt``) owns everything else.
* Leaf-aware cross-shard reductions (global-norm clip, drift) do not add
  hooks here: they combine ``model_psum`` + ``worker_psum_scalar`` through
  ``base_opt.make_grad_sq_fn`` with a sharded/replicated mask, so
  replicated leaves are never double-counted across model shards.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import topology
from repro.kernels import topk_compress

PyTree = Any


def _sparse_payload(x, r, ratio):
    """Per-slot top-k payload of the error-feedback signal.

    ``x`` is a (L, ...) boundary-delta leaf, ``r`` its residual (same
    shape, f32).  The transmitted signal is ``x + r``; its magnitude top-k
    payload crosses the wire, and the untransmitted remainder becomes the
    new residual — no signal is silently dropped, it is delayed.  Returns
    ``(values, indices, spec, new_residual)`` with (L, blocks, k) payloads.
    """
    sig = x.astype(jnp.float32) + r
    L = sig.shape[0]
    vals, idx, spec = topk_compress.sparsify_batch(sig.reshape(L, -1), ratio)
    blocks, be, _ = spec
    dense = topk_compress.reconstruct(vals, idx, be)
    new_resid = (sig.reshape(L, blocks, be) - dense).reshape(sig.shape)
    return vals, idx, spec, new_resid


def _split_pairs(pairs: PyTree) -> tuple[PyTree, PyTree]:
    """Unzip a tree of (a, b) leaf pairs into two trees."""
    is_pair = lambda p: isinstance(p, tuple)  # noqa: E731
    return (
        jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
        jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair),
    )


def bind_loss(loss_fn, backend):
    """Bind a backend-aware loss (anything exposing ``bind_backend``) to the
    backend the round executes on; plain ``(params, batch)`` callables pass
    through unchanged.  This is how TP-aware losses (``repro.models.tp``)
    reach the model-axis reduction hooks without widening the loss API."""
    bind = getattr(loss_fn, "bind_backend", None)
    return bind(backend) if bind is not None else loss_fn


class PendingMean:
    """In-flight handle of a ``worker_mean_start`` call.

    The collective is ISSUED at the program position of the ``start`` call;
    the handle pins its result until ``worker_mean_done`` consumes it.  The
    overlap contract lives in the DATAFLOW, not in the handle: because
    nothing between start and done depends on the averaged value, XLA's
    latency-hiding scheduler is free to lower the mesh backend's all-reduce
    as an ``all-reduce-start`` / ``all-reduce-done`` pair that runs behind
    the intervening compute (the next round's inner steps).  On the axis
    oracle the mean is simply computed eagerly and held — "an eager mean
    held one round" — which is the numerical reference for the mesh path.

    Handles are plain trace-time Python objects: they never cross a jit
    boundary and must be consumed inside the program that issued them.
    """

    __slots__ = ("tree",)

    def __init__(self, tree: PyTree):
        self.tree = tree


class AxisBackend:
    """Array-axis oracle: workers = leading axis 0 of every leaf."""

    kind = "axis"
    batch_axes: tuple[str, ...] = ()  # workers consume their batch whole
    model_axes: tuple[str, ...] = ()  # no tensor parallelism on the oracle
    model_shards: int = 1

    def __init__(self, num_workers: int):
        self.num_workers = num_workers

    @property
    def local_workers(self) -> int:
        return self.num_workers

    # -- reductions ---------------------------------------------------------
    def pmean_scalar(self, x: jnp.ndarray) -> jnp.ndarray:
        """Mean over workers of an already-locally-averaged scalar."""
        return x

    def grad_mean(self, tree: PyTree) -> PyTree:
        """Within-worker gradient sync over batch shards (hierarchical
        layouts).  The oracle has no batch axes — each worker's gradient is
        already the mean over its whole batch — so this is the identity."""
        return tree

    def worker_psum_scalar(self, x: jnp.ndarray) -> jnp.ndarray:
        """Sum over the WORKER axes only of an already model-complete scalar
        (e.g. a drift sum whose sharded-leaf contributions were psummed over
        ``model`` by ``base_opt.make_grad_sq_fn`` — never psum a per-device
        scalar over worker AND model jointly: that would double-count
        model-replicated contributions).  Identity on the oracle — sums over
        the leading axis already cover every worker."""
        return x

    # -- model-axis hooks (tensor parallelism; identity on the oracle) ------
    def model_psum(self, x: jnp.ndarray) -> jnp.ndarray:
        """Sum of partial activations over the model shards — where a row-
        parallel matmul (and the backward of a column-parallel one) deposits
        its reduction.  The oracle holds full parameters, so partial sums
        are already complete."""
        return x

    def model_pmax(self, x: jnp.ndarray) -> jnp.ndarray:
        """Max over model shards (vocab-parallel softmax stabilization)."""
        return x

    def model_index(self):
        """This device's position along the model axes (vocab offsets)."""
        return 0

    def worker_mean(self, tree: PyTree, dtype=None, mask=None) -> PyTree:
        """Exact average over the worker axis; drops the leading axis.

        ``dtype`` controls the precision OF THE COLLECTIVE (a §Perf knob:
        bf16 halves boundary traffic); the result is fp32 either way.

        ``mask`` (optional, shape ``(num_workers,)``, float) is the per-round
        PARTICIPATION vector: the weighted mean ``sum_i mask_i x_i / sum_i
        mask_i`` drops masked-out (straggler) contributions from the exact
        average.  It is a runtime INPUT, not a compile-time constant, so
        changing masks never recompiles; an all-ones mask is bit-identical
        to the unmasked path.  At least one entry must be nonzero — the
        elastic coordinator guarantees this."""
        if mask is None:

            def avg(x):
                acc = x.astype(dtype) if dtype is not None else x.astype(jnp.float32)
                return jnp.mean(acc, axis=0).astype(jnp.float32)

            return jax.tree.map(avg, tree)

        wsum = jnp.sum(mask.astype(jnp.float32))

        def avg_masked(x):
            acc = x.astype(dtype) if dtype is not None else x.astype(jnp.float32)
            m = mask.astype(acc.dtype).reshape(mask.shape + (1,) * (acc.ndim - 1))
            return (jnp.sum(acc * m, axis=0) / wsum.astype(acc.dtype)).astype(
                jnp.float32
            )

        return jax.tree.map(avg_masked, tree)

    def worker_mean_start(self, tree: PyTree, dtype=None, mask=None) -> PendingMean:
        """Kick off an exact worker average without consuming it.

        Oracle semantics: the mean is computed eagerly (same math as
        ``worker_mean``) and held in a ``PendingMean`` until
        ``worker_mean_done`` — the stale-boundary overlap's reference
        backend ("an eager mean held one round")."""
        return PendingMean(self.worker_mean(tree, dtype, mask=mask))

    def worker_mean_done(self, pending: PendingMean) -> PyTree:
        """Consume the average a ``worker_mean_start`` issued."""
        return pending.tree

    def worker_mean_sparse(
        self,
        tree: PyTree,
        residual: PyTree,
        ratio: float,
        dtype=None,
        mask=None,
    ) -> tuple[PyTree, PyTree]:
        """Compressed exact average with error feedback (DeMo-style top-k).

        Per worker slot: signal = leaf + residual; the per-block magnitude
        top-k payload of the signal is what would cross the wire (``dtype``
        is the wire precision of the VALUES; indices are always s32), and
        signal − sparse(signal) becomes the new residual.  Returns
        ``(mean_tree, new_residual)``: the (mask-weighted) mean of the
        sparsified signals with the leading worker axis dropped, plus the
        per-worker residual to carry.  The oracle compresses eagerly —
        the numerical reference for the mesh all-gather path.  At
        ratio=1.0 every entry survives and the mean equals the dense
        ``worker_mean`` of signal to f32 rounding.
        """
        wsum = (
            jnp.sum(mask.astype(jnp.float32))
            if mask is not None
            else jnp.float32(self.num_workers)
        )

        def one(x, r):
            vals, idx, spec, new_resid = _sparse_payload(x, r, ratio)
            acc = vals.astype(dtype) if dtype is not None else vals
            if mask is not None:
                acc = acc * mask.astype(acc.dtype).reshape(-1, 1, 1)
            dense = topk_compress.reconstruct(
                acc.astype(jnp.float32), idx, spec[1]
            )
            mean = jnp.sum(dense, axis=0) / wsum
            return mean.reshape(x.shape[1:]).astype(jnp.float32), new_resid

        return _split_pairs(jax.tree.map(one, tree, residual))

    def worker_mean_sparse_start(
        self,
        tree: PyTree,
        residual: PyTree,
        ratio: float,
        dtype=None,
        mask=None,
    ) -> tuple[PendingMean, PyTree]:
        """Sparse variant of ``worker_mean_start``: kick off the compressed
        average, return ``(handle, new_residual)``.  The residual update is
        immediate (it is local); only the mean is held for
        ``worker_mean_done``."""
        mean, new_resid = self.worker_mean_sparse(
            tree, residual, ratio, dtype, mask=mask
        )
        return PendingMean(mean), new_resid

    def mean_keepdims(self, x: jnp.ndarray) -> jnp.ndarray:
        """Every worker slot replaced by the mean; shape preserved."""
        if x.ndim == 0:
            return x
        return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)

    # -- broadcast / permute ------------------------------------------------
    def bcast(self, tree: PyTree, dtype) -> PyTree:
        """Attach a (replicated) leading worker axis."""
        W = self.num_workers
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None].astype(dtype), (W,) + x.shape),
            tree,
        )

    def roll(self, x: jnp.ndarray, hop: int) -> jnp.ndarray:
        """Roll along the worker axis: slot i receives from (i - hop) % m."""
        return jnp.roll(x, hop, axis=0)

    def roll_tree(self, tree: PyTree, hop: int) -> PyTree:
        return jax.tree.map(lambda x: self.roll(x, hop), tree)


class MeshBackend:
    """shard_map collectives: workers sharded over ``axis_names`` mesh axes.

    Only valid INSIDE a ``shard_map`` over a mesh carrying ``axis_names``.
    Rolls require one worker per device along the worker axes (local worker
    axis of size 1); pure-averaging bases (local/ar) also work with several
    workers per device.

    ``batch_axes`` (hierarchical layouts) are the additional mesh axes each
    worker's batch is sharded over: ``grad_mean`` all-reduces gradients over
    them every inner step (within-pod DP sync), and scalar loss means reduce
    over worker AND batch axes jointly.  Parameter-state collectives (exact
    average, gossip rolls, buffer averaging) stay on the worker axes only —
    the per-worker state is REPLICATED over the batch axes and every batch-
    axis replica computes the identical update once gradients are synced.

    ``model_axes`` (tensor-parallel layouts) are the mesh axes every
    parameter leaf is model-sharded over: the ``model_psum`` / ``model_pmax``
    hooks execute the loss's Megatron-style activation reductions over them,
    and NOTHING ELSE reduces over model — state collectives operate on the
    local model shard (which is what shrinks boundary traffic by 1/TP), and
    scalar losses are already model-replicated after the loss's own psum.
    """

    kind = "mesh"

    def __init__(
        self,
        axis_names: tuple[str, ...],
        num_workers: int,
        num_devices: int,
        batch_axes: tuple[str, ...] = (),
        model_axes: tuple[str, ...] = (),
        model_shards: int = 1,
    ):
        if num_workers % num_devices:
            raise ValueError(
                f"num_workers={num_workers} not divisible by the "
                f"{num_devices} devices of worker axes {axis_names}"
            )
        self.axis_names = tuple(axis_names)
        self.num_workers = num_workers
        self.num_devices = num_devices
        self.batch_axes = tuple(batch_axes)
        self.model_axes = tuple(model_axes)
        self.model_shards = model_shards
        # jax collectives accept a single name or a tuple of names (the
        # flattened, row-major index over the named axes).
        self.axis_entry = (
            self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]
        )
        self.batch_entry = (
            self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        ) if self.batch_axes else None
        self.model_entry = (
            self.model_axes if len(self.model_axes) > 1 else self.model_axes[0]
        ) if self.model_axes else None
        # scalar reductions span worker + batch axes, NOT model: model-axis
        # replicas hold identical scalars once the loss has psummed its
        # activations, while e.g. AR gradient buffers DIFFER per model shard
        # and must never be averaged across model.
        scalar_axes = self.axis_names + self.batch_axes
        self.scalar_entry = scalar_axes if len(scalar_axes) > 1 else scalar_axes[0]

    @property
    def local_workers(self) -> int:
        return self.num_workers // self.num_devices

    # -- reductions ---------------------------------------------------------
    def pmean_scalar(self, x: jnp.ndarray) -> jnp.ndarray:
        # worker AND batch axes: with equal-size batch shards, the mean of
        # per-shard means over (pod, data) equals the mean of per-worker
        # (full pod batch) means — matching the oracle's scalar.
        return jax.lax.pmean(x, self.scalar_entry)

    def grad_mean(self, tree: PyTree) -> PyTree:
        """Within-pod gradient sync: mean over the batch (``data``) axes —
        the hierarchical layout's every-inner-step all-reduce.  One
        collective per leaf (ONE total on packed state).  No-op on layouts
        without batch axes."""
        if not self.batch_axes:
            return tree
        return jax.tree.map(lambda g: jax.lax.pmean(g, self.batch_entry), tree)

    def worker_psum_scalar(self, x: jnp.ndarray) -> jnp.ndarray:
        # worker axes only: the summand must already be model-complete (and
        # is replicated over the batch axes, which hold no distinct state).
        # There is deliberately no worker+model joint psum in this API — it
        # would count model-REPLICATED contributions once per shard; leaf-
        # aware reductions go through ``base_opt.make_grad_sq_fn``.
        return jax.lax.psum(x, self.axis_entry)

    # -- model-axis hooks (tensor parallelism) ------------------------------
    def model_psum(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.model_entry is None:
            return x
        return jax.lax.psum(x, self.model_entry)

    def model_pmax(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.model_entry is None:
            return x
        return jax.lax.pmax(x, self.model_entry)

    def model_index(self):
        if self.model_entry is None:
            return 0
        return jax.lax.axis_index(self.model_entry)

    def worker_mean(self, tree: PyTree, dtype=None, mask=None) -> PyTree:
        if mask is None:

            def avg(x):
                acc = x.astype(dtype) if dtype is not None else x.astype(jnp.float32)
                # local mean over the (equal-size) local worker axis, then the
                # cross-device mean — lowers to an all-reduce over the mesh
                # axes.
                return jax.lax.pmean(jnp.mean(acc, axis=0), self.axis_entry).astype(
                    jnp.float32
                )

            return jax.tree.map(avg, tree)

        # ``mask`` enters the shard_map body as the LOCAL (local_workers,)
        # slice of the global participation vector.  The participant count is
        # ONE extra 4-byte scalar all-reduce per boundary (budgeted by the
        # contract as ``mask-psum``); the per-leaf weighted sums reuse the
        # same all-reduce the unmasked pmean would issue, at the same wire
        # dtype — so straggler tolerance costs one scalar collective.
        wsum = jax.lax.psum(jnp.sum(mask.astype(jnp.float32)), self.axis_entry)

        def avg_masked(x):
            acc = x.astype(dtype) if dtype is not None else x.astype(jnp.float32)
            m = mask.astype(acc.dtype).reshape(mask.shape + (1,) * (acc.ndim - 1))
            num = jax.lax.psum(jnp.sum(acc * m, axis=0), self.axis_entry)
            return (num / wsum.astype(num.dtype)).astype(jnp.float32)

        return jax.tree.map(avg_masked, tree)

    def worker_mean_start(self, tree: PyTree, dtype=None, mask=None) -> PendingMean:
        """Issue the boundary all-reduce HERE, consume it later.

        The ``lax.pmean`` (and, masked, the participation psum) is traced at
        the call site — the top of the overlapped round, BEFORE the inner
        loop — with no data dependence on the intervening compute, so XLA
        lowers it as an async ``all-reduce-start``/``all-reduce-done`` pair
        scheduled behind the inner steps on async-capable backends.  The
        census is unchanged: pre-optimization HLO shows the same one
        all-reduce per unit over the worker axes (``analysis.hlo`` counts
        ``-start`` forms as the op; ``-done`` carries no new traffic)."""
        return PendingMean(self.worker_mean(tree, dtype, mask=mask))

    def worker_mean_done(self, pending: PendingMean) -> PyTree:
        """Consume the average a ``worker_mean_start`` issued."""
        return pending.tree

    def worker_mean_sparse(
        self,
        tree: PyTree,
        residual: PyTree,
        ratio: float,
        dtype=None,
        mask=None,
    ) -> tuple[PyTree, PyTree]:
        """Compressed exact average: all-gather the sparse payload instead
        of all-reducing the dense buffer.

        Each device sparsifies its local workers' error-feedback signal
        (signal = leaf + residual; remainder → new residual, kept local),
        then TWO all-gathers per unit cross the worker axes — the values
        at the wire ``dtype`` and the s32 indices — shrinking boundary
        traffic to ``payload/dense ∝ k / block_elems`` (budgeted by the
        contract as ``boundary-gather`` / ``boundary-gather-idx``).  Every
        device reconstructs the dense sum from the full payload locally
        and divides by the participant count.  ``mask`` scales each
        worker's VALUES before the gather (masked-out workers transmit
        zeros) — after the residual update, so stragglers keep
        accumulating their error feedback — and the divisor becomes the
        ``mask-psum`` participant count, exactly like masked
        ``worker_mean``.
        """
        wsum = (
            jax.lax.psum(jnp.sum(mask.astype(jnp.float32)), self.axis_entry)
            if mask is not None
            else jnp.float32(self.num_workers)
        )

        def one(x, r):
            vals, idx, spec, new_resid = _sparse_payload(x, r, ratio)
            acc = vals.astype(dtype) if dtype is not None else vals
            if mask is not None:
                acc = acc * mask.astype(acc.dtype).reshape(-1, 1, 1)
            vals_g = jax.lax.all_gather(acc, self.axis_entry, tiled=True)
            idx_g = jax.lax.all_gather(idx, self.axis_entry, tiled=True)
            dense = topk_compress.reconstruct(
                vals_g.astype(jnp.float32), idx_g, spec[1]
            )
            mean = jnp.sum(dense, axis=0) / wsum
            return mean.reshape(x.shape[1:]).astype(jnp.float32), new_resid

        return _split_pairs(jax.tree.map(one, tree, residual))

    def worker_mean_sparse_start(
        self,
        tree: PyTree,
        residual: PyTree,
        ratio: float,
        dtype=None,
        mask=None,
    ) -> tuple[PendingMean, PyTree]:
        """Issue the sparse boundary gathers HERE, consume the mean later.

        Same dataflow contract as ``worker_mean_start``: the all-gathers
        are traced at the call site with no dependence on the intervening
        compute, so XLA may lower them as async start/done pairs hidden
        behind the inner steps.  The residual update is local and returned
        immediately."""
        mean, new_resid = self.worker_mean_sparse(
            tree, residual, ratio, dtype, mask=mask
        )
        return PendingMean(mean), new_resid

    def mean_keepdims(self, x: jnp.ndarray) -> jnp.ndarray:
        # worker AND batch axes in ONE collective: for AR gradient averaging
        # this is the global batch mean directly (no separate grad_mean hop
        # needed); for buffer averaging the batch-axis replicas are identical
        # so the extra axes change nothing numerically.
        if x.ndim == 0:
            return x
        m = jnp.mean(x, axis=0, keepdims=True)
        return jnp.broadcast_to(jax.lax.pmean(m, self.scalar_entry), x.shape)

    # -- broadcast / permute ------------------------------------------------
    def bcast(self, tree: PyTree, dtype) -> PyTree:
        L = self.local_workers
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None].astype(dtype), (L,) + x.shape),
            tree,
        )

    def roll(self, x: jnp.ndarray, hop: int) -> jnp.ndarray:
        if self.local_workers != 1:
            raise ValueError(
                "mesh rolls need one worker per device "
                f"(local_workers={self.local_workers})"
            )
        perm = topology.ppermute_perm(self.num_devices, hop)
        return jax.lax.ppermute(x, self.axis_entry, perm)

    def roll_tree(self, tree: PyTree, hop: int) -> PyTree:
        return jax.tree.map(lambda x: self.roll(x, hop), tree)


CommBackend = AxisBackend | MeshBackend


def default_backend(num_workers: int) -> AxisBackend:
    return AxisBackend(num_workers)
