"""SlowMo (Algorithm 1) — slow momentum over communication-efficient base optimizers.

One jitted **round** = ``tau`` base-optimizer steps + (optional) exact average
+ slow-momentum outer update:

    for k in 0..tau-1:   x^(i) <- x^(i) - gamma * d^(i)      (base optimizer)
    x_tau = (1/m) sum_i x^(i)                                 (ALLREDUCE, line 6)
    u <- beta * u + (x_0 - x_tau) / gamma                     (line 7)
    x_0 <- x_0 - alpha * gamma * u                            (line 8)

The m workers live on a leading array axis of every parameter leaf; on the
production mesh that axis is sharded over the ``data`` (and ``pod``) mesh
axes, so the exact average lowers to an all-reduce and gossip lowers to
collective-permutes.

All worker-axis communication goes through the ``CommBackend`` seam
(``repro.core.comm``): the default ``AxisBackend`` executes collectives as
plain array ops on the leading axis (single-device oracle), while the
``MeshBackend`` — driven by ``repro.distributed.spmd.make_spmd_slowmo_round``
— runs the same round body inside ``shard_map`` with ``lax.pmean`` /
``lax.ppermute`` over real mesh axes.  To exercise the mesh path on a
CPU-only host, set ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
BEFORE importing jax (see tests/test_spmd.py).

Orthogonally, ``packed=True`` swaps the per-leaf state pytrees for a few
contiguous ``(rows, 1024)`` flat buffers (``repro.core.packing``): the round
body is identical (everything here tree-maps), but the boundary then costs
one kernel launch and ONE collective instead of one per parameter leaf, and
the tree layout is materialized only at the ``loss_fn`` boundary.
Equivalence with the tree layout is pinned by ``tests/test_packed.py``.

``overlap_boundary=True`` hides line 6 behind the NEXT round's inner steps
(staleness 1): the round issues the all-reduce of last round's endpoint
snapshot before its inner loop and consumes it afterwards, applying lines
7–8 to double-buffered outer state (``SlowMoState.boundary`` /
``stale_outer``) — see ``_outer_update_stale`` and
``docs/architecture.md`` §6.  Stale-vs-exact drift is pinned by
``repro.analysis.stale_drift`` and ``tests/test_overlap.py``.
Recovered special cases (tested):

* base='local', tau=1, alpha=1, beta>0 ........ large-batch SGD + momentum
* base='local', tau>1, alpha=1, beta=0 ........ Local SGD
* base='local'/Adam, tau>1, beta>0 ............ BMUF
* W=1, beta=0, alpha in (0,1] ................. Lookahead
* exact_average=False ......................... SGP-SlowMo-noaverage (§6)
* beta=0, alpha=1, buffer_strategy='average' .. double-averaging (Yu et al.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import base_opt, comm, gossip, packing
from .base_opt import InnerOptConfig, InnerOptState
from .gossip import GossipConfig, GossipState
from .packing import PackSpec

PyTree = Any

BASES = ("local", "sgp", "osgp", "dpsgd", "ar")
BUFFER_STRATEGIES = ("reset", "maintain", "average")


@dataclasses.dataclass(frozen=True)
class SlowMoConfig:
    """Full specification of a SlowMo algorithm instance."""

    num_workers: int
    tau: int = 12
    alpha: float = 1.0  # slow learning rate (paper: 1.0 is uniformly best)
    beta: float = 0.7  # slow momentum factor (paper: 0.4–0.8)
    base: str = "local"  # base algorithm
    inner: InnerOptConfig = dataclasses.field(default_factory=InnerOptConfig)
    buffer_strategy: str = "reset"
    exact_average: bool = True  # False => SlowMo-noaverage (§6)
    param_dtype: Any = jnp.float32
    track_drift: bool = False
    use_pallas: bool = False  # fused Pallas kernels (interpret on CPU): the
    # lines-7-8 outer update, AND the inner Nesterov step whenever the base
    # evaluates gradients at the params themselves (sgd+nesterov, non-gossip)
    average_dtype: Any = None  # dtype of the exact-average all-reduce (None=f32)
    unroll_inner: bool = False  # unroll the tau inner steps (the contract
    # auditor's census then counts each step's collectives)
    packed: bool = False  # flat-buffer state: one kernel launch / collective
    # per boundary instead of one per leaf (see core/packing.py); requires a
    # PackSpec threaded through init_slowmo / make_slowmo_round.
    masked_average: bool = False  # the round takes a per-round participation
    # mask (W,) as a RUNTIME input and line 6 becomes the weighted mean over
    # the unmasked workers (straggler tolerance; see comm.worker_mean).  An
    # all-ones mask is bit-identical to the unmasked round, and changing the
    # mask never recompiles.  Requires exact_average.
    overlap_boundary: bool = False  # staleness-1 boundary: the line-6
    # all-reduce of round t's endpoint is ISSUED at the top of round t+1 and
    # consumed after its inner steps, so lines 7-8 apply the PREVIOUS round's
    # average to double-buffered outer state (state.boundary / .stale_outer)
    # — the collective overlaps the inner compute instead of serializing the
    # boundary.  Requires exact_average; see comm.worker_mean_start.
    compress_ratio: float | None = None  # DeMo-style top-k boundary
    # compression: line 6 averages the magnitude top-k payload of each
    # worker's boundary DELTA (endpoint − outer anchor) plus its error-
    # feedback residual (SlowMoState.residual), all-gathering sparse
    # (values, indices) payloads instead of all-reducing the dense buffer
    # (see comm.worker_mean_sparse / kernels.topk_compress).  The ratio is
    # the surviving fraction per block; 1.0 keeps every entry (≡ dense to
    # f32 rounding), None disables.  Requires exact_average.  Composes
    # with masked_average and overlap_boundary.

    def __post_init__(self):
        if self.base not in BASES:
            raise ValueError(f"unknown base algorithm: {self.base!r}")
        if self.buffer_strategy not in BUFFER_STRATEGIES:
            raise ValueError(f"unknown buffer strategy: {self.buffer_strategy!r}")
        if self.num_workers < 1 or self.tau < 1:
            raise ValueError("num_workers and tau must be >= 1")
        if self.masked_average and not self.exact_average:
            raise ValueError(
                "masked_average masks the line-6 exact average; it has no "
                "meaning under exact_average=False (noaverage)"
            )
        if self.overlap_boundary and not self.exact_average:
            raise ValueError(
                "overlap_boundary overlaps the line-6 exact average; it has "
                "no meaning under exact_average=False (noaverage)"
            )
        if self.compress_ratio is not None:
            if not self.exact_average:
                raise ValueError(
                    "compress_ratio compresses the line-6 exact average; it "
                    "has no meaning under exact_average=False (noaverage)"
                )
            if not (0.0 < self.compress_ratio <= 1.0):
                raise ValueError(
                    f"compress_ratio must be in (0, 1], got {self.compress_ratio}"
                )

    @property
    def gossip_config(self) -> GossipConfig:
        kind = self.base if self.base in ("sgp", "osgp", "dpsgd") else "none"
        # gossip honors average_dtype the same way the exact average does:
        # the PERMUTED message (the wire transfer) is cast, accumulation
        # stays fp32 (see gossip.mix).
        return GossipConfig(
            kind=kind, num_workers=self.num_workers, comm_dtype=self.average_dtype
        )

    @property
    def slowmo_active(self) -> bool:
        return not (self.beta == 0.0 and self.alpha == 1.0)


class TPMasks(NamedTuple):
    """Which parts of the state are model-sharded, for leaf-aware cross-shard
    reductions (global-norm clip, drift) on tensor-parallel backends.

    ``tree``: bool per params-tree leaf (True = sharded) — used whenever a
    round phase carries the per-leaf layout.  ``packed``: a
    ``packing.ShardRanges`` of static per-group element ranges of the
    sharded slots in the per-shard buffer layout
    (``packing.ShardedPackSpec.sharded_ranges``) — used on packed phases.
    Built by ``repro.distributed.spmd.build_spmd_round``; irrelevant (None)
    on TP-free backends."""

    tree: Any = None
    packed: Any = None


class SlowMoState(NamedTuple):
    params: PyTree  # (W, ...) worker copies, param_dtype
    inner: InnerOptState  # base optimizer buffers, leading W
    gossip: GossipState
    outer_params: PyTree  # x_{t,0}, fp32; (W, ...) iff exact_average=False
    slow_u: PyTree  # u_t, fp32; same layout as outer_params
    step: jnp.ndarray  # global inner step counter
    outer_step: jnp.ndarray  # t
    # overlap_boundary double buffers (None — i.e. structurally absent —
    # unless cfg.overlap_boundary; trailing position keeps the leaf order of
    # every pre-overlap state intact):
    boundary: PyTree = None  # in-flight boundary snapshot: last round's
    # (debiased) inner endpoint, (W, ...) at param_dtype — the tree the next
    # round's stale all-reduce averages
    stale_outer: PyTree = None  # the outer iterate the snapshot's trajectory
    # STARTED from (the line-7 anchor), fp32, replicated like outer_params
    boundary_mask: jnp.ndarray | None = None  # (W,) participation mask
    # captured WITH the snapshot (masked_average only): the mask rides the
    # in-flight boundary it masks
    residual: PyTree = None  # compress_ratio only: per-worker error-feedback
    # remainder, (W, ...) fp32, shaped like params — the part of each
    # boundary signal the top-k payload did NOT transmit, added back into
    # the next round's signal so no update is silently dropped.  Packs,
    # shards, and checkpoints like slow momentum (trailing position keeps
    # pre-compression leaf order intact).


def _bcast_workers(tree: PyTree, W: int, dtype) -> PyTree:
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None].astype(dtype), (W,) + x.shape), tree
    )


def make_state_pack_spec(cfg: SlowMoConfig, params0: PyTree, layout=None) -> PackSpec:
    """The static packing index for ``cfg.packed`` state: built from the
    parameter tree AFTER the ``param_dtype`` cast, so every trainer / test /
    checkpoint that derives it from the same model agrees on the layout.
    ``params0`` may be concrete arrays or ``jax.eval_shape`` structs.

    ``layout`` (a ``WorkerLayout`` with model axes of size > 1) switches to
    the shard-major ``packing.ShardedPackSpec``: buffers pack one row block
    per model shard — sliced along the dims ``sharding.model_spec_tail``
    marks — so the mapped TP round operates on the local shard and the
    boundary all-reduce moves 1/TP of the bytes."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, cfg.param_dtype), params0
    )
    tp = getattr(layout, "model_shard", 1) if layout is not None else 1
    if tp > 1:
        from ..distributed import sharding  # lazy: distributed imports core

        return packing.make_sharded_pack_spec(
            shapes, sharding.model_shard_dims(shapes, tp), tp
        )
    return packing.make_pack_spec(shapes)


def init_slowmo(
    cfg: SlowMoConfig, params0: PyTree, pack: PackSpec | None = None
) -> SlowMoState:
    """Initialize from a single (worker-axis-free) parameter pytree.

    With ``cfg.packed`` every state component is a ``packing.Packed`` flat
    buffer — ``(W, rows, 1024)`` for per-worker leaves, ``(rows, 1024)`` for
    the replicated outer iterate — instead of a parameter-shaped pytree.
    """
    W = cfg.num_workers
    if cfg.packed:
        pack = pack or make_state_pack_spec(cfg, params0)

        def bcast(b):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), b
            )

        params = bcast(
            pack.pack(jax.tree.map(lambda x: x.astype(cfg.param_dtype), params0))
        )
        outer = pack.pack(params0, dtype=jnp.float32)
        if not cfg.exact_average:
            outer = bcast(outer)
        inner = InnerOptState(
            h=pack.zeros(lead=(W,), dtype=jnp.float32),
            v=pack.zeros(lead=(W,), dtype=jnp.float32)
            if cfg.inner.kind == "adam"
            else pack.scalars(),
            count=jnp.zeros((), jnp.int32),
        )
    else:
        params = _bcast_workers(params0, W, cfg.param_dtype)
        outer = jax.tree.map(lambda x: x.astype(jnp.float32), params0)
        if not cfg.exact_average:
            outer = _bcast_workers(params0, W, jnp.float32)
        inner = base_opt.init_inner_state(cfg.inner, params)
    u = jax.tree.map(jnp.zeros_like, outer)
    boundary = stale = bmask = None
    if cfg.overlap_boundary:
        # Round 0's in-flight boundary: a per-worker copy of the initial
        # iterate anchored at itself, so the first stale update is a no-op
        # (its pseudo-gradient is exactly zero) and real averages take
        # effect from round 1 on — staleness-1 from the very first round.
        # Copies, not aliases: every leaf is donated independently.
        boundary = jax.tree.map(jnp.copy, params)
        stale = jax.tree.map(jnp.copy, outer)
        if cfg.masked_average:
            bmask = jnp.ones((W,), jnp.float32)
    residual = None
    if cfg.compress_ratio is not None:
        # error feedback starts empty: round 0's signal is exactly its delta
        residual = (
            pack.zeros(lead=(W,), dtype=jnp.float32)
            if cfg.packed
            else jax.tree.map(
                lambda x: jnp.zeros((W,) + x.shape, jnp.float32), params0
            )
        )
    return SlowMoState(
        params=params,
        inner=inner,
        gossip=gossip.init_gossip_state(cfg.gossip_config, params),
        outer_params=outer,
        slow_u=u,
        step=jnp.zeros((), jnp.int32),
        outer_step=jnp.zeros((), jnp.int32),
        boundary=boundary,
        stale_outer=stale,
        boundary_mask=bmask,
        residual=residual,
    )


def make_inner_step(
    cfg: SlowMoConfig,
    loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
    backend: comm.CommBackend | None = None,
    pack: PackSpec | None = None,
    grad_pack: PackSpec | None = None,
    sq_fn=None,
):
    """Build one base-optimizer step over all W workers.

    ``loss_fn(params_one_worker, batch_one_worker) -> scalar loss``; a
    backend-aware loss (``comm.bind_loss`` protocol, e.g. a TP-aware
    ``models.tp.TPLoss``) is bound to ``backend`` here, so its model-axis
    reductions execute on whichever backend the round runs on.
    Returns ``step_fn((params, inner, gossip_state, step), batch) ->
    (carry, mean_loss)`` where batch leaves have leading worker axis W
    (its local shard on the mesh backend).

    With ``pack`` (packed mode) the carry holds flat buffers; the parameter
    tree is materialized ONLY at the ``loss_fn`` boundary (slice + reshape),
    gradients are packed straight back, and everything downstream — AR
    gradient averaging, momentum, gossip mixing — runs on whole buffers, so
    per-step collectives are one per buffer instead of one per leaf.

    ``grad_pack`` (tree-carry mode on hierarchical backends) keeps the carry
    in the per-leaf layout — the unpacked param tree is CACHED across the
    inner loop instead of re-unpacked every step — and packs ONLY the
    gradients around the batch-axis sync, so the per-step ``data``
    all-reduce still moves one flat buffer.

    ``sq_fn`` (``base_opt.make_grad_sq_fn``) is the global sum-of-squares
    the clip uses; on tensor-parallel backends it must match the layout the
    gradients have AT apply_step time (packed vs tree) so the clip norm
    spans every model shard without double-counting replicated leaves.
    """
    backend = backend or comm.AxisBackend(cfg.num_workers)
    loss_fn = comm.bind_loss(loss_fn, backend)
    vgrad = jax.vmap(jax.value_and_grad(_with_stats(loss_fn), has_aux=True))
    gcfg = cfg.gossip_config

    def step_fn(carry, batch, lr):
        params, inner, gstate, step = carry
        # SGP/OSGP evaluate gradients at the de-biased iterate z = x / w.
        if gcfg.kind in ("sgp", "osgp"):
            z = gossip.debias(params, gstate.w)
        else:
            z = params
        z_tree = pack.unpack(z) if pack is not None else z
        with jax.named_scope("fwd_bwd"):
            (losses, stats), grads = vgrad(z_tree, batch)
        if pack is not None:
            grads = pack.pack(grads, dtype=jnp.float32)
        with jax.named_scope("grad_sync"):
            if cfg.base == "ar":
                # ALLREDUCE baseline: average gradients across workers every
                # step.  mean_keepdims reduces over worker AND batch axes in
                # one collective, so this subsumes the hierarchical within-pod
                # sync.
                grads = jax.tree.map(backend.mean_keepdims, grads)
            elif grad_pack is not None and backend.batch_axes:
                # tree-carry on a hierarchical backend: pack the gradients
                # just for the within-pod sync (ONE collective per buffer) and
                # unpack the reduced result back into the cached tree layout.
                grads = grad_pack.unpack(
                    backend.grad_mean(grad_pack.pack(grads, dtype=jnp.float32))
                )
            else:
                # Hierarchical layouts: within-pod DP sync — all-reduce the
                # gradients over the backend's batch axes so every device in
                # a pod steps with the gradient of the full pod batch
                # (identity on the oracle and on layouts without batch axes).
                # Runs AFTER packing (one collective on packed state) and
                # BEFORE clipping/momentum inside apply_step, so the inner
                # optimizer sees exactly the bigger-batch worker's gradient.
                grads = backend.grad_mean(grads)
        with jax.named_scope("inner_opt"):
            params, inner = base_opt.apply_step(
                cfg.inner,
                inner,
                params,
                grads,
                lr,
                z=z if gcfg.kind in ("sgp", "osgp") else None,
                use_pallas=cfg.use_pallas,
                sq_fn=sq_fn,
            )
        with jax.named_scope("gossip"):
            params, gstate = gossip.mix(gcfg, gstate, params, step, backend)
        loss = backend.pmean_scalar(jnp.mean(losses))
        stats = {k: backend.pmean_scalar(jnp.mean(v)) for k, v in stats.items()}
        return (params, inner, gstate, step + 1), (loss, stats)

    return step_fn


def _with_stats(loss_fn):
    """``loss_fn`` as a ``(loss, stats)`` function: a loss may return its
    own counters (``{name: scalar}``) beside the loss, or the loss alone."""

    def fn(params, batch):
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    return fn


def _debias_endpoint(cfg: SlowMoConfig, state: SlowMoState) -> PyTree:
    """The inner-loop endpoint in iterate space: SGP/OSGP trajectories carry
    biased params and are de-biased by the push-sum weights; everyone else's
    params ARE the iterate."""
    if cfg.gossip_config.kind in ("sgp", "osgp"):
        return gossip.debias(state.params, state.gossip.w)
    return state.params


def _line6(cfg: SlowMoConfig, state: SlowMoState, backend, mask):
    """Line 6 of Algorithm 1: the averaged endpoint ``x_tau`` the slow
    momentum steps toward, and the error-feedback residual after it."""
    if cfg.exact_average and cfg.compress_ratio is not None:
        # Compressed line 6: average the top-k payload of each worker's
        # DELTA against the shared outer anchor (plus its error-feedback
        # residual), then rebuild x_tau = anchor + mean(sparse delta).
        # Compressing the delta, not the iterate, is what makes top-k
        # meaningful — the delta is the tau-step movement, small and
        # concentrated, while the iterate's energy is everywhere.
        delta = jax.tree.map(
            lambda e, o: e.astype(jnp.float32) - o[None],
            _debias_endpoint(cfg, state),
            state.outer_params,
        )
        mean_delta, new_resid = backend.worker_mean_sparse(
            delta,
            state.residual,
            cfg.compress_ratio,
            cfg.average_dtype,
            mask=mask,
        )
        x_tau = jax.tree.map(lambda o, d: o + d, state.outer_params, mean_delta)
        return x_tau, new_resid
    if cfg.exact_average:
        # Line 6: exact average over the worker axis -> all-reduce.
        x_tau = backend.worker_mean(
            _debias_endpoint(cfg, state), cfg.average_dtype, mask=mask
        )
    else:
        # noaverage (§6): skip line 6; each worker applies the slow update
        # to its own drift (outer state carries the worker axis).
        x_tau = jax.tree.map(
            lambda x: x.astype(jnp.float32), _debias_endpoint(cfg, state)
        )
    return x_tau, state.residual


@jax.named_scope("boundary")
def outer_update(
    cfg: SlowMoConfig,
    state: SlowMoState,
    lr,
    backend: comm.CommBackend | None = None,
    mask=None,
    stale_handle: comm.PendingMean | None = None,
) -> SlowMoState:
    """Lines 6–8 of Algorithm 1 plus the buffer strategy (line 2).

    This code is layout-agnostic: on packed state every tree here has ~one
    leaf per dtype group, so line 6 lowers to a single all-reduce and the
    fused lines-7-8 kernel runs as a single ``pallas_call`` over the whole
    buffer (the packed rows are block-aligned — no pad copies).

    ``mask`` (iff ``cfg.masked_average``) is the per-round participation
    vector: line 6 becomes the weighted mean over unmasked workers, so a
    straggler's stale contribution drops out; everything downstream (slow
    momentum, broadcast, buffer strategy) is unchanged and the broadcast
    hands the straggler the fresh averaged iterate — automatic catch-up.

    ``cfg.overlap_boundary`` switches to the STALE boundary: the consumed
    average is last round's in-flight snapshot (``stale_handle``, issued by
    the round body before the inner loop — or started here for direct
    callers, losing the overlap but not the numerics), line 7 anchors at
    ``state.stale_outer`` (the iterate that snapshot's trajectory started
    from) while line 8 moves the CURRENT ``state.outer_params``, and the
    double buffers rotate: the new anchor is this round's outer iterate and
    the new snapshot is this round's (debiased) endpoint.  ``mask`` is then
    NOT applied to the consumed average (its mask rode in with the
    snapshot as ``state.boundary_mask``) — it is captured as the mask of
    the snapshot taken here."""
    from ..kernels import ops as kops  # local import: kernels are optional

    backend = backend or comm.AxisBackend(cfg.num_workers)
    if cfg.overlap_boundary:
        return _outer_update_stale(cfg, state, lr, backend, mask, stale_handle, kops)
    with jax.named_scope("line6"):
        x_tau, new_resid = _line6(cfg, state, backend, mask)
    with jax.named_scope("lines7_8"):
        new_outer, new_u = kops.slowmo_outer_update(
            state.outer_params,
            x_tau,
            state.slow_u,
            gamma=lr,
            alpha=cfg.alpha,
            beta=cfg.beta,
            use_pallas=cfg.use_pallas,
        )

    if cfg.exact_average:
        new_params = backend.bcast(new_outer, cfg.param_dtype)
    else:
        new_params = jax.tree.map(
            lambda x: x.astype(cfg.param_dtype), new_outer
        )

    # Line 2: reset / maintain / average the base-optimizer buffers.
    inner = state.inner
    if cfg.buffer_strategy == "reset":
        inner = base_opt.reset_buffers(cfg.inner, inner)
    elif cfg.buffer_strategy == "average":
        inner = base_opt.average_buffers(inner, backend)

    # Gossip de-bias weights restart at 1 after an exact average.
    gstate = state.gossip
    if cfg.exact_average and cfg.gossip_config.kind in ("sgp", "osgp"):
        gstate = gossip.init_gossip_state(
            cfg.gossip_config, new_params, num_workers=backend.local_workers
        )

    return SlowMoState(
        params=new_params,
        inner=inner,
        gossip=gstate,
        outer_params=new_outer,
        slow_u=new_u,
        step=state.step,
        outer_step=state.outer_step + 1,
        residual=new_resid,
    )


def _outer_update_stale(
    cfg: SlowMoConfig, state: SlowMoState, lr, backend, mask, handle, kops
) -> SlowMoState:
    """Stale-boundary lines 6–8 (``cfg.overlap_boundary``): consume LAST
    round's average, rotate the double buffers, snapshot THIS round's
    endpoint.  See ``outer_update`` for the contract; the index bookkeeping:

        entering round r:  outer O_r, anchor A_r = O_{r-1},
                           snapshot S_r = round r-1's endpoint (from A_r)
        u_r     = beta * u_{r-1} + (A_r - avg(S_r)) / gamma      (line 7)
        O_{r+1} = O_r - alpha * gamma * u_r                      (line 8)
        rotate:  anchor' = O_r,  snapshot' = round r's endpoint
    """
    new_resid = state.residual
    with jax.named_scope("line6"):
        if handle is None:
            # direct caller — no round body issued the collective early;
            # start it here (identical numerics, no overlap to gain)
            handle, new_resid = _stale_mean_start(cfg, state, backend)
        if cfg.compress_ratio is not None:
            # the in-flight value is the mean sparse DELTA against the
            # anchor the snapshot's trajectory started from; rebuild the
            # averaged endpoint at that same anchor (line 7 then subtracts
            # it again)
            x_tau = jax.tree.map(
                lambda o, d: o + d,
                state.stale_outer,
                backend.worker_mean_done(handle),
            )
        else:
            x_tau = backend.worker_mean_done(handle)

    # Line 7 anchored at the snapshot's start iterate.  The fused kernel
    # moves its x-input (the anchor) — that output is discarded (DCE'd);
    # only the momentum comes from it, line 8 moves the CURRENT iterate.
    with jax.named_scope("lines7_8"):
        _, new_u = kops.slowmo_outer_update(
            state.stale_outer,
            x_tau,
            state.slow_u,
            gamma=lr,
            alpha=cfg.alpha,
            beta=cfg.beta,
            use_pallas=cfg.use_pallas,
        )
        slow_step = cfg.alpha * lr
        new_outer = jax.tree.map(
            lambda o, u: o - slow_step * u, state.outer_params, new_u
        )

    # rotate the double buffers: the next in-flight snapshot is this round's
    # (debiased) endpoint, anchored at the iterate its trajectory started
    # from — the CURRENT outer, captured before line 8 replaced it
    snapshot = jax.tree.map(
        lambda x: x.astype(cfg.param_dtype), _debias_endpoint(cfg, state)
    )
    new_params = backend.bcast(new_outer, cfg.param_dtype)

    # Line 2 (buffer strategy) and the gossip-weight restart keep their
    # per-round timing: every round still ends with the outer broadcast.
    inner = state.inner
    if cfg.buffer_strategy == "reset":
        inner = base_opt.reset_buffers(cfg.inner, inner)
    elif cfg.buffer_strategy == "average":
        inner = base_opt.average_buffers(inner, backend)
    gstate = state.gossip
    if cfg.gossip_config.kind in ("sgp", "osgp"):
        gstate = gossip.init_gossip_state(
            cfg.gossip_config, new_params, num_workers=backend.local_workers
        )

    return SlowMoState(
        params=new_params,
        inner=inner,
        gossip=gstate,
        outer_params=new_outer,
        slow_u=new_u,
        step=state.step,
        outer_step=state.outer_step + 1,
        boundary=snapshot,
        stale_outer=state.outer_params,
        boundary_mask=(
            jnp.asarray(mask, jnp.float32) if mask is not None else None
        ),
        residual=new_resid,
    )


def _stale_mean_start(cfg: SlowMoConfig, state: SlowMoState, backend):
    """Issue the stale boundary's line-6 mean of the in-flight snapshot
    (compressed: of its delta against its anchor), under the mask that rode
    in with it.  Returns the pending handle and the residual after it."""
    bmask = state.boundary_mask if cfg.masked_average else None
    if cfg.compress_ratio is not None:
        return backend.worker_mean_sparse_start(
            _stale_delta(state),
            state.residual,
            cfg.compress_ratio,
            cfg.average_dtype,
            mask=bmask,
        )
    return backend.worker_mean_start(
        state.boundary, cfg.average_dtype, mask=bmask
    ), state.residual


def _stale_delta(state: SlowMoState) -> PyTree:
    """The in-flight snapshot's delta against the anchor its trajectory
    started from — the signal the compressed stale boundary averages."""
    return jax.tree.map(
        lambda b, o: b.astype(jnp.float32) - o[None],
        state.boundary,
        state.stale_outer,
    )


def make_slowmo_round(
    cfg: SlowMoConfig,
    loss_fn: Callable[[PyTree, PyTree], jnp.ndarray],
    backend: comm.CommBackend | None = None,
    pack: PackSpec | None = None,
    tp_masks: TPMasks | None = None,
):
    """Build the jittable round function.

    ``round_fn(state, batches, lr) -> (state, metrics)`` where every leaf of
    ``batches`` is shaped ``(tau, W, ...)`` and ``lr`` is the (fast) learning
    rate gamma_t used for all tau steps of this round.  With
    ``cfg.masked_average`` the signature grows a fourth positional input —
    ``round_fn(state, batches, lr, mask)`` with ``mask`` the float ``(W,)``
    participation vector fed to the line-6 weighted average (a traced input:
    no recompile across masks; the drift metric and buffer averaging stay
    unmasked — they are diagnostics/strategy over the full slot set).

    ``backend`` selects how worker collectives execute: the default
    ``AxisBackend`` runs them on the leading array axis; a ``MeshBackend``
    (installed by ``repro.distributed.spmd``) runs the identical body under
    shard_map with real collectives.

    ``pack`` (required iff ``cfg.packed``) is the static PackSpec the state
    was initialized with (``make_state_pack_spec``): the state then lives in
    flat buffers and the boundary (exact average + outer update) is one
    collective + one kernel launch.  Inside the tau-step inner loop the
    layout is chosen per base algorithm: bases that communicate parameters
    every step (SGP/OSGP/D-PSGD) or need whole-buffer gradient reductions
    over the worker axes (AR) run fully packed so those per-step collectives
    are one-per-buffer; the ``local`` base never communicates PARAMETERS
    inside the loop, so its inner loop carries the tree layout — the
    unpacked param tree is cached across all tau steps instead of being
    re-unpacked at every ``loss_fn`` boundary — and converts to flat buffers
    at the round boundary only.  On a hierarchical backend (``batch_axes``)
    the local base still all-reduces GRADIENTS within the pod every step;
    there the gradients alone are packed around that sync (``grad_pack``),
    keeping it at one collective per buffer while the params stay cached.

    ``tp_masks`` (required iff the backend has model shards AND clip_norm or
    track_drift is on) carries the leaf-aware sharded/replicated split both
    reductions need to span model shards correctly — built by
    ``distributed.spmd.build_spmd_round`` from the same ``model_spec_tail``
    rules that shard the state.
    """
    if cfg.packed and pack is None:
        raise ValueError("cfg.packed requires the PackSpec the state was built with")
    if pack is not None and not cfg.packed:
        raise ValueError("got a PackSpec but cfg.packed is False")
    backend = backend or comm.AxisBackend(cfg.num_workers)
    # tree-carry packing is correct exactly when the inner loop never
    # communicates parameters: 'local' workers only touch their own copy
    # (their gradient sync, if any, packs just the grads around the
    # collective), so params/momentum convert at the round boundary only.
    tree_inner = pack is not None and cfg.base == "local"
    grad_pack = pack if (tree_inner and getattr(backend, "batch_axes", ())) else None
    tp = getattr(backend, "model_shards", 1)
    if tp > 1 and (cfg.inner.clip_norm or cfg.track_drift) and tp_masks is None:
        raise ValueError(
            "clip_norm / track_drift on a tensor-parallel backend need "
            "TPMasks (which leaves are model-sharded) — the spmd round "
            "builder derives them; direct callers must pass tp_masks"
        )
    tp_masks = tp_masks if tp > 1 else None
    # the clip sees gradients in whatever layout the inner loop carries;
    # drift sees the round-boundary state layout (packed iff cfg.packed)
    inner_mask = drift_mask = None
    if tp_masks is not None:
        inner_mask = tp_masks.tree if (tree_inner or pack is None) else tp_masks.packed
        drift_mask = tp_masks.packed if cfg.packed else tp_masks.tree
    clip_sq_fn = base_opt.make_grad_sq_fn(backend, inner_mask)
    # a loss that returns counters beside itself names them in ``.stats``
    stats_names = tuple(getattr(loss_fn, "stats", ()))
    step_fn = make_inner_step(
        cfg,
        loss_fn,
        backend,
        None if tree_inner else pack,
        grad_pack=grad_pack,
        sq_fn=clip_sq_fn,
    )

    def _round(state: SlowMoState, batches: PyTree, lr, mask):
        lr = jnp.asarray(lr, jnp.float32)
        pending = None
        new_resid = state.residual
        if cfg.overlap_boundary:
            # issue LAST round's boundary all-reduce before the inner loop:
            # nothing below depends on its result until the outer update
            # consumes it, so the collective is free to overlap the tau
            # inner steps (all-reduce-start/-done on async backends); its
            # mask rode in with the snapshot it averages.  Compressed, the
            # in-flight value is the mean sparse DELTA of the snapshot
            # against its anchor; the residual update is local and lands in
            # the mid-round state below.
            with jax.named_scope("boundary"), jax.named_scope("line6"):
                pending, new_resid = _stale_mean_start(cfg, state, backend)

        def body(k, acc):
            carry, sums = acc
            batch_k = jax.tree.map(lambda x: x[k], batches)
            carry, out = step_fn(carry, batch_k, lr)
            return carry, jax.tree.map(jnp.add, sums, out)

        inner0, params0 = state.inner, state.params
        if tree_inner:
            # one unpack per ROUND (amortized over tau inner steps); the
            # SGD second-moment placeholder / none-gossip state never mix
            # with parameter-shaped trees, so they pass through packed.
            params0 = pack.unpack(state.params)
            inner0 = InnerOptState(
                h=pack.unpack(state.inner.h),
                v=pack.unpack(state.inner.v)
                if cfg.inner.kind == "adam"
                else state.inner.v,
                count=state.inner.count,
            )
        carry0 = (params0, inner0, state.gossip, state.step)
        zero = jnp.zeros((), jnp.float32)
        acc0 = (carry0, (zero, {name: zero for name in stats_names}))
        if cfg.unroll_inner:
            acc = acc0
            for k in range(cfg.tau):
                acc = body(k, acc)
            (params, inner, gstate, step), loss_sum = acc
        else:
            (params, inner, gstate, step), loss_sum = jax.lax.fori_loop(
                0, cfg.tau, body, acc0
            )
        if tree_inner:
            params = pack.pack(params)
            inner = InnerOptState(
                h=pack.pack(inner.h, dtype=jnp.float32),
                v=pack.pack(inner.v, dtype=jnp.float32)
                if cfg.inner.kind == "adam"
                else inner.v,
                count=inner.count,
            )
        state = SlowMoState(
            params=params,
            inner=inner,
            gossip=gstate,
            outer_params=state.outer_params,
            slow_u=state.slow_u,
            step=step,
            outer_step=state.outer_step,
            boundary=state.boundary,
            stale_outer=state.stale_outer,
            boundary_mask=state.boundary_mask,
            residual=new_resid,
        )
        loss_sum, stats_sum = loss_sum
        metrics = {"loss": loss_sum / cfg.tau}
        if stats_sum:
            metrics["stats"] = {k: v / cfg.tau for k, v in stats_sum.items()}
        if cfg.track_drift:
            # mean drift ||x^(i) - x_bar||^2: the per-worker sum of squares
            # goes through the leaf-aware sq_fn so that on tensor-parallel
            # backends sharded leaves psum over 'model' while replicated
            # leaves count once; the worker sum is a psum over the worker
            # axes only (the summand is already model-complete).
            mean_p = backend.worker_mean(state.params)
            diff = jax.tree.map(
                lambda x, m: x.astype(jnp.float32) - m[None], state.params, mean_p
            )
            per_worker = base_opt.make_grad_sq_fn(backend, drift_mask)(diff)
            drift = backend.worker_psum_scalar(jnp.sum(per_worker))
            metrics["drift"] = drift / cfg.num_workers
        state = outer_update(
            cfg, state, lr, backend, mask=mask, stale_handle=pending
        )
        return state, metrics

    if cfg.masked_average:

        def round_fn(state: SlowMoState, batches: PyTree, lr, mask):
            return _round(state, batches, lr, jnp.asarray(mask, jnp.float32))

    else:

        def round_fn(state: SlowMoState, batches: PyTree, lr):
            return _round(state, batches, lr, None)

    return round_fn


# ---------------------------------------------------------------------------
# Named presets matching the paper's baselines (Table 1 / App. C).
# ---------------------------------------------------------------------------

def _preset_specs(beta: float, inner: InnerOptConfig) -> dict[str, dict]:
    adam = dataclasses.replace(inner, kind="adam")
    return {
        # base algorithms (no slow momentum: beta=0, alpha=1)
        "local_sgd": dict(base="local", beta=0.0, alpha=1.0),
        "local_adam": dict(base="local", beta=0.0, alpha=1.0, inner=adam),
        "sgp": dict(base="sgp", beta=0.0, alpha=1.0),
        "osgp": dict(base="osgp", beta=0.0, alpha=1.0),
        "dpsgd": dict(base="dpsgd", beta=0.0, alpha=1.0),
        "ar_sgd": dict(base="ar", beta=0.0, alpha=1.0, tau=1),
        "ar_adam": dict(base="ar", beta=0.0, alpha=1.0, tau=1, inner=adam),
        # SlowMo on top (BMUF == local_* + slowmo)
        "local_sgd+slowmo": dict(base="local", beta=beta),
        "local_adam+slowmo": dict(
            base="local", beta=beta, inner=adam, buffer_strategy="maintain"
        ),
        "sgp+slowmo": dict(base="sgp", beta=beta),
        "osgp+slowmo": dict(base="osgp", beta=beta),
        "sgp+slowmo-noaverage": dict(base="sgp", beta=beta, exact_average=False),
        # comparisons
        "double_averaging": dict(
            base="local", beta=0.0, alpha=1.0, buffer_strategy="average"
        ),
        "lookahead": dict(base="local", beta=0.0, alpha=0.5),
    }


#: Every named preset, in table order — the audit CLI sweeps this.
PRESET_NAMES: tuple[str, ...] = tuple(_preset_specs(0.7, InnerOptConfig()))


def preset(
    name: str,
    num_workers: int,
    tau: int = 12,
    beta: float = 0.7,
    inner: InnerOptConfig | None = None,
    **kw,
) -> SlowMoConfig:
    """Paper baselines by name: '<base>' or '<base>+slowmo' and friends."""
    inner = inner or InnerOptConfig()
    table = _preset_specs(beta, inner)
    if name not in table:
        raise KeyError(f"unknown preset {name!r}; have {sorted(table)}")
    spec = dict(num_workers=num_workers, tau=tau, inner=inner)
    spec.update(table[name])
    spec.update(kw)
    return SlowMoConfig(**spec)
