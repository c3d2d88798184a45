"""Mesh-lowered SlowMo execution: the round under ``jax.shard_map``.

This is the path that turns the array-axis *simulation* of m workers into a
distributable SPMD program.  ``make_spmd_slowmo_round`` takes the same
``SlowMoConfig`` + ``loss_fn`` as ``slowmo.make_slowmo_round`` plus a
``WorkerLayout`` (``repro.launch.mesh``), and runs the identical round body
inside ``shard_map`` with the worker axis sharded over the layout's worker
mesh axes:

* the exact average (Algorithm 1 line 6) executes as ``jax.lax.pmean`` and
  lowers to an ``all-reduce`` over the worker axes;
* SGP/OSGP/D-PSGD gossip rolls execute as ``jax.lax.ppermute`` and lower to
  ``collective-permute``s;
* each device holds only its local shard of the per-worker state (the
  leading worker axis of every leaf shrinks to ``W / num_worker_devices``,
  i.e. 1 in the one-worker-per-device layouts).

The GLOBAL state layout is identical to the array-axis path — ``init_slowmo``
states, checkpoints and metrics are interchangeable between backends; only
the execution differs.  Equivalence is pinned by ``tests/test_spmd.py``.

Host-CPU recipe (no accelerator needed): set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment
BEFORE the first jax import, build a worker mesh with
``launch.mesh.make_spmd_layout(8)``, and the lowered HLO contains real
``all-reduce`` / ``collective-permute`` ops (checked via
``analysis.hlo``).

Hierarchical layouts (``make_layout(style="hierarchical")`` /
``launch.mesh.make_hierarchical_layout``) run through the same wrapper: the
SlowMo worker axis shards over ``pod`` only, each worker's batch additionally
shards over the layout's ``batch_axes`` (``data``), and the backend's
``grad_mean`` hook all-reduces gradients over ``data`` every inner step —
within-pod data parallelism under the slow cross-pod momentum, the paper's
actual node-level setup (and BMUF's block structure).  A (pods, data)
hierarchical round is numerically a flat ``pods``-worker round whose
per-worker batch is the concatenation of the pod's data shards; equivalence
and the two-level replica-group structure are pinned by
``tests/test_hierarchical_spmd.py``.

Tensor-parallel layouts (``make_hierarchical_layout(pods, data, tp)`` /
``make_spmd_layout(workers, tp)``) run the FULL (pod, data, model) mesh
through the same wrapper: every parameter-shaped leaf is additionally
model-sharded over the ``model`` axes via the ``model_spec_tail`` rules
(``distributed.sharding``), the loss executes Megatron-style — column-parallel
in, row-parallel out, ``psum`` over ``model`` through the backend's
model-axis hooks (``repro.models.tp``) — and every state collective (the
per-step ``data`` gradient sync, the boundary ``pod`` all-reduce, gossip
permutes) moves only the LOCAL model shard, so boundary traffic shrinks by
1/TP.  Packed TP states use the shard-major ``packing.ShardedPackSpec``;
equivalence with the TP-free round and the three-level collective structure
are pinned by ``tests/test_tp_spmd.py``.

Global-norm clipping and ``track_drift`` compose with TP: the round builder
derives ``slowmo.TPMasks`` (which leaves are model-sharded) from the same
rules that sharded the state, so both reductions psum sharded-leaf
contributions over ``model`` and count replicated leaves exactly once —
pinned against the TP-free mesh by ``tests/test_unified_tp.py``.

``overlap_boundary`` configs run through the same wrapper unchanged: the
double-buffered overlap state (``boundary``, worker-sharded like params;
``stale_outer``, replicated; ``boundary_mask``, worker-sharded) picks up
its specs from ``sharding.spmd_state_specs``, rides the same state
donation (its leaves append after the blocking leaves, so existing alias
indices are stable), and the stale average — traced before the inner loop
with no consumer until after it — is free to lower as an
``all-reduce-start``/``-done`` pair (docs/architecture.md §6, pinned by
``tests/test_overlap.py``).

``compress_ratio`` configs also run unchanged: the boundary average swaps
the dense worker all-reduce for two ``all-gather``s of the statically
shaped magnitude top-k payload — ``(values, indices)`` per 64Ki-element
block of each worker's boundary delta — followed by a local dense
reconstruct + mean (``comm.MeshBackend.worker_mean_sparse``).  The
per-worker error-feedback ``residual`` is worker-sharded like params and
rides the same state donation; composition with ``overlap_boundary`` and
the elastic participation mask is pinned by ``tests/test_compress.py``
(docs/architecture.md §7).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import comm, packing, slowmo
from ..core.slowmo import SlowMoConfig
from ..launch import mesh as mesh_lib
from ..launch.mesh import WorkerLayout
from . import sharding

PyTree = Any


def _validate(cfg: SlowMoConfig, layout: WorkerLayout) -> int:
    if not layout.worker_axes:
        raise ValueError("spmd path needs a layout with worker mesh axes")
    mesh_lib.validate_spmd_model_axes(layout)
    for a in layout.batch_axes:
        if a not in layout.mesh.axis_names:
            raise ValueError(
                f"batch axis {a!r} is not a mesh axis "
                f"(mesh has {tuple(layout.mesh.axis_names)})"
            )
        if a in layout.worker_axes:
            raise ValueError(
                f"axis {a!r} cannot be both a worker axis and a batch axis"
            )
    n_dev = int(np.prod([layout.mesh.shape[a] for a in layout.worker_axes]))
    if cfg.num_workers % n_dev:
        raise ValueError(
            f"num_workers={cfg.num_workers} must be divisible by the "
            f"{n_dev} devices of worker axes {layout.worker_axes}"
        )
    needs_permute = cfg.gossip_config.kind != "none"
    if needs_permute and cfg.num_workers != n_dev:
        raise ValueError(
            "gossip bases need one worker per device on the mesh path "
            f"(num_workers={cfg.num_workers}, worker devices={n_dev})"
        )
    return n_dev


def _validate_batches(layout: WorkerLayout, batches: PyTree) -> None:
    """Eager check that every (tau, W, B, ...) batch leaf's B dim splits
    over the layout's batch axes — a clear message instead of the sharding
    error jit would raise deep inside shard_map."""
    shard = layout.batch_shard
    if shard == 1:
        return
    for leaf in jax.tree.leaves(batches):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 3 and shape[2] % shard:
            raise ValueError(
                f"per-worker batch {shape[2]} (batch leaf {shape}) must be "
                f"divisible by the {shard}-way batch axes "
                f"{layout.batch_axes} of the hierarchical layout"
            )


def _validate_tp_loss(layout: WorkerLayout, loss_fn) -> None:
    """TP layouts shard every rule-matched parameter leaf, so the loss MUST
    be backend-aware (the ``comm.bind_loss`` protocol, e.g.
    ``models.tp.TPLoss``) to deposit its model-axis psums; a plain
    ``(params, batch)`` callable would consume the shards as if they were
    full parameters and silently train on 1/TP of every contraction."""
    if layout.model_shard > 1 and not hasattr(loss_fn, "bind_backend"):
        raise ValueError(
            "TP layouts need a backend-aware loss (models.tp.TPLoss / "
            "make_tp_loss): a plain loss cannot psum its model-sharded "
            "matmuls over the 'model' axes"
        )


def mesh_backend(cfg: SlowMoConfig, layout: WorkerLayout) -> comm.MeshBackend:
    n_dev = _validate(cfg, layout)
    model_axes = tuple(
        a
        for a in layout.model_axes
        if a in layout.mesh.axis_names and layout.mesh.shape[a] > 1
    )
    return comm.MeshBackend(
        layout.worker_axes,
        cfg.num_workers,
        n_dev,
        batch_axes=layout.batch_axes,
        model_axes=model_axes,
        model_shards=layout.model_shard,
    )


def build_spmd_round(
    cfg: SlowMoConfig,
    loss_fn: Callable[[PyTree, PyTree], Any],
    layout: WorkerLayout,
    state: PyTree,
    batches: PyTree,
    pack=None,
):
    """Explicit builder: returns the jitted shard-mapped round function.

    ``state`` / ``batches`` supply the pytree structure for the Partition-
    Specs (concrete arrays or ``jax.eval_shape`` structs both work); use the
    returned function's ``.lower(state, batches, lr)`` for HLO inspection.

    ``pack`` (iff ``cfg.packed``) is the state's PackSpec: the mapped body
    then carries flat buffers, so the boundary collectives are one
    all-reduce / collective-permute per buffer instead of per leaf.

    The state argument is DONATED: XLA reuses its buffers for the returned
    state (the shapes match 1:1), eliminating the per-round full-state copy.
    Donation is real on every backend including CPU — the input arrays (and
    anything aliasing their buffers, e.g. the params tree the state was
    built from) are DELETED by the call, so callers must rebind and never
    touch a state object after passing it in.
    """
    backend = mesh_backend(cfg, layout)
    _validate_tp_loss(layout, loss_fn)
    _validate_batches(layout, batches)
    body_pack = pack
    if pack is not None and backend.model_shards > 1:
        if not isinstance(pack, packing.ShardedPackSpec):
            raise ValueError(
                "packed TP rounds need the shard-major ShardedPackSpec — "
                "build it with make_state_pack_spec(cfg, params, layout=layout)"
            )
        if pack.num_shards != backend.model_shards:
            raise ValueError(
                f"PackSpec was built for {pack.num_shards} model shards but "
                f"the layout has {backend.model_shards}"
            )
        # inside the mapped body every device holds one shard block, laid
        # out by the plain per-shard spec
        body_pack = pack.shard
    elif isinstance(pack, packing.ShardedPackSpec):
        raise ValueError(
            "got a ShardedPackSpec but the layout has no model axes of size > 1"
        )
    tp_masks = None
    if backend.model_shards > 1 and (cfg.inner.clip_norm or cfg.track_drift):
        # leaf-aware sharded/replicated split for the cross-shard global
        # norm (clip) and drift: sharded contributions psum over 'model',
        # replicated leaves count once.  Derived from the SAME rules that
        # sharded the state (ShardedPackSpec.shard_dims on packed state,
        # model_spec_tail on the per-leaf tree).
        if pack is not None:
            tp_masks = slowmo.TPMasks(
                tree=pack.tree_sharded_mask(), packed=pack.sharded_ranges()
            )
        else:
            tp_masks = slowmo.TPMasks(
                tree=sharding.model_sharded_mask(
                    state.params, backend.model_shards
                )
            )
    body = slowmo.make_slowmo_round(
        cfg,
        loss_fn,
        backend,
        pack=body_pack,
        tp_masks=tp_masks,
    )
    state_specs = sharding.spmd_state_specs(
        layout, state, exact_average=cfg.exact_average
    )
    batch_specs = sharding.spmd_batch_specs(layout, batches)
    # every metric (loss, drift, the model's counters) is a replicated
    # scalar: one spec, a prefix of whatever the round returns
    metric_specs = P()
    in_specs = (state_specs, batch_specs, P())
    if cfg.masked_average:
        # the (W,) participation mask is a fourth traced input, sharded over
        # the worker axes — masks change per round without recompiling
        in_specs = in_specs + (sharding.spmd_mask_spec(layout),)
    mapped = jax.shard_map(
        body,
        mesh=layout.mesh,
        in_specs=in_specs,
        out_specs=(state_specs, metric_specs),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=0)


def make_spmd_slowmo_round(
    cfg: SlowMoConfig,
    loss_fn: Callable[[PyTree, PyTree], Any],
    layout: WorkerLayout,
    pack=None,
):
    """Drop-in replacement for ``jax.jit(slowmo.make_slowmo_round(...))``.

    The shard_map wrapping needs the state/batch pytree structure, which is
    only known at call time — the first call (per structure) builds and
    caches the jitted mapped function.  The state argument is donated (see
    ``build_spmd_round``).
    """
    _validate(cfg, layout)
    _validate_tp_loss(layout, loss_fn)
    cache: dict = {}

    def round_fn(state, batches, lr, *mask):
        # re-check every call, not just on cache miss: the cache is keyed on
        # pytree STRUCTURE, so a later call with the same structure but a
        # ragged batch shape would otherwise skip the eager check and die
        # deep inside shard_map instead.  ``*mask`` is the (W,) participation
        # vector, required (as one extra positional) iff cfg.masked_average.
        _validate_batches(layout, batches)
        key = (jax.tree.structure(state), jax.tree.structure(batches))
        if key not in cache:
            cache[key] = build_spmd_round(cfg, loss_fn, layout, state, batches, pack)
        return cache[key](state, batches, lr, *mask)

    round_fn.build = lambda state, batches: build_spmd_round(
        cfg, loss_fn, layout, state, batches, pack
    )
    return round_fn


def make_survivor_round(
    cfg: SlowMoConfig,
    loss_fn: Callable[[PyTree, PyTree], Any],
    layout: WorkerLayout,
    survivors,
    pack=None,
):
    """Rebuild the compiled round for an ordered survivor set.

    At an elastic boundary the membership changed: this derives the survivor
    ``WorkerLayout`` (``launch.mesh.make_survivor_layout`` — the surviving
    devices, worker axes collapsed to one), the survivor ``SlowMoConfig``
    (``num_workers=len(survivors)``, which re-derives gossip topology, hops
    and replica groups for the new count), and a fresh shard-mapped round
    over them.  The PackSpec is worker-count-independent and is reused
    as-is.  Returns ``(new_cfg, new_layout, round_fn)``; the state must be
    resized separately (``repro.elastic.reconfigure``).
    """
    import dataclasses

    new_layout = mesh_lib.make_survivor_layout(layout, survivors)
    new_cfg = dataclasses.replace(cfg, num_workers=new_layout.num_workers)
    return new_cfg, new_layout, make_spmd_slowmo_round(
        new_cfg, loss_fn, new_layout, pack=pack
    )


def serve_mesh_backend(layout: WorkerLayout) -> comm.MeshBackend:
    """MeshBackend of the tensor-parallel SERVE step: no SlowMo workers —
    the layout's worker axes (size 1 on ``make_spmd_layout(1, tp)``) only
    satisfy the backend's axis bookkeeping; the step reaches the model-axis
    hooks exclusively (``model_psum``/``model_pmax``/``model_index``), so
    every collective it issues reduces over ``model`` — which is exactly
    what ``analysis.contract.serve_step_contract`` audits."""
    wax = layout.worker_axes or layout.data_axes
    if not wax:
        raise ValueError("serve layout needs at least one non-model mesh axis")
    n_dev = int(np.prod([layout.mesh.shape[a] for a in wax]))
    model_axes = tuple(
        a
        for a in layout.model_axes
        if a in layout.mesh.axis_names and layout.mesh.shape[a] > 1
    )
    return comm.MeshBackend(
        wax,
        n_dev,
        n_dev,
        model_axes=model_axes,
        model_shards=layout.model_shard,
    )


def make_paged_serve_step(
    model_cfg,
    layout: WorkerLayout,
    params: PyTree,
    pool_shape: tuple,
    *,
    prefill_self: bool,
    temperature: float,
):
    """The continuous-batching serve step under ``shard_map``: sharded
    params, kv-head-sharded page pools, replicated scheduler inputs
    (page_table / pos / num_new / tokens / key), and vocab-parallel sampling
    so the returned ``(B,)`` token ids are already model-complete.

    Page pools are DONATED (argnums 1, 2): the step rewrites them in place
    every call, so XLA reuses their buffers — callers must rebind, exactly
    like the training round's donated state.  One builder call per static
    ``prefill_self`` mode; token-buffer widths (chunk vs 1) share the
    returned function through jit's shape cache.
    """
    from ..models import dense, tp as tp_mod

    backend = serve_mesh_backend(layout)
    param_specs = sharding.serve_param_specs(layout, params)
    pool_spec = sharding.serve_pool_spec(layout, pool_shape)

    def body(params, k_pages, v_pages, page_table, pos, num_new, tokens, key):
        logits, k_pages, v_pages = dense.paged_step(
            model_cfg,
            params,
            k_pages,
            v_pages,
            page_table,
            pos,
            num_new,
            tokens,
            backend=backend,
            prefill_self=prefill_self,
        )
        sampled = tp_mod.sample_tokens(
            backend, logits, model_cfg.vocab_size, temperature, key
        )
        return sampled, k_pages, v_pages

    mapped = jax.shard_map(
        body,
        mesh=layout.mesh,
        in_specs=(param_specs, pool_spec, pool_spec, P(), P(), P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(1, 2))


def state_shardings(cfg: SlowMoConfig, layout: WorkerLayout, state: PyTree) -> PyTree:
    """NamedSharding tree to ``jax.device_put`` a global SlowMoState onto the
    worker mesh (optional — jit would move it on first call anyway)."""
    specs = sharding.spmd_state_specs(layout, state, exact_average=cfg.exact_average)
    return jax.tree.map(lambda s: NamedSharding(layout.mesh, s), specs)
