"""Mesh construction + worker-layout mapping.

Every builder here is a FUNCTION (module import never touches jax device
state); on a CPU-only host set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first jax
import to build meshes over N placeholder devices.

Worker layouts:
* ``flat``        — paper-faithful: one SlowMo worker per data-axis row.
* ``hierarchical``— the paper's ACTUAL experimental regime (each node an
                    AllReduce DP group, SlowMo across nodes — the BMUF block
                    structure): one worker per pod; within-pod DP gradients
                    sync every step over fast ICI (the layout's
                    ``batch_axes``), SlowMo handles only the cross-pod
                    (slow) links.  Both run through the shard_map execution
                    path (``repro.distributed.spmd``).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def device_grid(shape, what: str = "mesh") -> np.ndarray:
    """The first ``prod(shape)`` devices arranged as ``shape``.

    A grid over every device follows the interconnect
    (``mesh_utils.create_device_mesh``: on a TPU slice neighbouring mesh
    coordinates are neighbouring chips, which ``jax.devices()`` list order
    does not promise); a grid over a subset keeps list order.
    """
    shape = tuple(int(d) for d in shape)
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices for a {what}, have {len(devs)}")
    if n == len(devs):
        return mesh_utils.create_device_mesh(shape, devices=devs)
    return np.asarray(devs[:n]).reshape(shape)


def make_worker_mesh(num_workers: int, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``num_workers`` devices: one worker per device.

    This is the entry mesh for the shard_map execution path
    (``repro.distributed.spmd``); on a CPU-only host set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<num_workers>``
    before the first jax import.
    """
    return Mesh(device_grid((num_workers,), "worker mesh"), (axis,))


def make_spmd_layout(num_workers: int, tp: int = 1) -> WorkerLayout:
    """WorkerLayout for the shard_map path: one worker per ``data`` row.

    ``tp > 1`` adds a ``model`` axis: each worker becomes a tensor-parallel
    group of ``tp`` devices holding model shards of its parameters (the loss
    must be TP-aware — see ``repro.models.tp``)."""
    if tp <= 1:
        mesh = make_worker_mesh(num_workers)
        return WorkerLayout(mesh, worker_axes=("data",), batch_axes=(), model_axes=())
    grid = device_grid((num_workers, tp), f"({num_workers} data x {tp} model) mesh")
    return make_layout(Mesh(grid, ("data", "model")), "flat", spmd=True)


def make_hierarchical_layout(pods: int, data: int, tp: int = 1) -> WorkerLayout:
    """Hierarchical (pod, data[, model]) WorkerLayout for the shard_map path.

    ``pods`` SlowMo workers, each an AllReduce DP group of ``data`` devices:
    the first ``pods * data * tp`` devices form the mesh, SlowMo state and
    the slow-momentum collectives live on ``pod``, each worker's batch is
    sharded (and its gradients synced every inner step) over ``data``.
    ``tp > 1`` makes every (pod, data) cell a tensor-parallel group of ``tp``
    devices along a ``model`` axis — the full production (pod, data, model)
    topology, with parameters model-sharded inside each worker and the
    loss's Megatron-style reductions psummed over ``model`` only.  On a
    CPU-only host set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before the first jax import.
    """
    what = f"({pods} pods x {data} data{f' x {tp} model' if tp > 1 else ''}) mesh"
    if tp <= 1:
        mesh = Mesh(device_grid((pods, data), what), ("pod", "data"))
    else:
        mesh = Mesh(device_grid((pods, data, tp), what), ("pod", "data", "model"))
    return make_layout(mesh, "hierarchical", spmd=True)


@dataclasses.dataclass(frozen=True)
class WorkerLayout:
    """How SlowMo workers map onto mesh axes."""

    mesh: Mesh
    worker_axes: tuple[str, ...]  # mesh axes forming the worker axis
    batch_axes: tuple[str, ...]  # remaining axes sharding each worker's batch
    model_axes: tuple[str, ...] = ("model",)

    @property
    def num_workers(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.worker_axes]))

    @property
    def batch_shard(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes])) or 1

    def effective_batch(self, per_worker_batch: int) -> int:
        """Global samples consumed per inner step.

        Hierarchical and flat layouts over the same mesh agree whenever the
        flat per-worker batch times the batch_shard equals the hierarchical
        per-worker batch — a pod IS one bigger-batch worker."""
        return max(self.num_workers, 1) * per_worker_batch

    @property
    def data_axes(self) -> tuple[str, ...]:
        """All non-model axes (used by serve-path batch sharding)."""
        return tuple(a for a in self.mesh.axis_names if a not in self.model_axes)

    @property
    def model_shard(self) -> int:
        """Tensor-parallel degree: total devices along the model axes that
        are actually present in the mesh (1 = no tensor parallelism)."""
        return (
            int(
                np.prod(
                    [
                        self.mesh.shape[a]
                        for a in self.model_axes
                        if a in self.mesh.axis_names
                    ]
                )
            )
            or 1
        )


def make_survivor_layout(layout: WorkerLayout, survivors) -> WorkerLayout:
    """The layout of the SURVIVING worker set after an elastic eviction.

    ``survivors`` is the ordered list of worker ids (slots along the
    flattened worker axes of ``layout``) that remain.  The surviving
    devices are selected — each worker keeps its physical devices, including
    its whole batch/model group on hierarchical/TP layouts — and the worker
    axes collapse to ONE axis (named after the first worker axis) of size
    ``len(survivors)``, because the survivor set need not factor over
    multiple axes.  Position ``j`` of the new worker axis is survivor
    ``survivors[j]``: the same ordered-survivor convention
    ``core.topology`` derives hops, mixing matrices and ppermute pairs
    from, so the rebuilt round's replica groups and gossip graph are the
    exponential graph of the surviving set.
    """
    from ..core import topology

    ids = topology.worker_order(survivors)
    if not layout.worker_axes:
        raise ValueError("survivor layouts need a layout with worker axes")
    W = layout.num_workers
    bad = [w for w in ids if w >= W]
    if bad:
        raise ValueError(f"survivor ids {bad} out of range for {W} workers")
    names = tuple(layout.mesh.axis_names)
    wdims = [names.index(a) for a in layout.worker_axes]
    other = [i for i in range(len(names)) if i not in wdims]
    # worker axes to the front, flattened row-major (the worker-id order),
    # then select the survivor rows
    devs = np.moveaxis(layout.mesh.devices, wdims, range(len(wdims)))
    devs = devs.reshape((W,) + tuple(devs.shape[len(wdims):]))
    sel = devs[np.asarray(ids)]
    new_names = (layout.worker_axes[0],) + tuple(names[i] for i in other)
    mesh = Mesh(sel, new_names)
    return WorkerLayout(
        mesh,
        worker_axes=(layout.worker_axes[0],),
        batch_axes=layout.batch_axes,
        model_axes=layout.model_axes,
    )


def validate_spmd_model_axes(layout: WorkerLayout) -> None:
    """THE model-axis rule of the shard_map path, shared by
    ``make_layout(spmd=True)`` and ``repro.distributed.spmd._validate``:
    model axes may have any size (tensor-parallel workers), but they must be
    DISJOINT from the worker and batch axes — a mesh axis cannot both shard
    parameters and carry SlowMo workers / batch shards."""
    for a in layout.model_axes:
        if a in layout.worker_axes:
            raise ValueError(
                f"axis {a!r} cannot be both a worker axis and a model axis"
            )
        if a in layout.batch_axes:
            raise ValueError(
                f"axis {a!r} cannot be both a batch axis and a model axis"
            )


def make_layout(mesh: Mesh, style: str = "flat", *, spmd: bool = False) -> WorkerLayout:
    """Map a mesh to a WorkerLayout; errors are raised EAGERLY with the
    offending axis named, not at lowering time.

    ``spmd=True`` additionally validates the layout for the shard_map
    execution path (``repro.distributed.spmd``): model axes (any size —
    tensor-parallel workers run through the mapped round) must be disjoint
    from the worker and batch axes.
    """
    axes = mesh.axis_names
    if style == "flat":
        layout = WorkerLayout(
            mesh, worker_axes=tuple(a for a in axes if a != "model"), batch_axes=()
        )
    elif style == "hierarchical":
        if "pod" not in axes:
            raise ValueError(
                f"hierarchical layout needs a 'pod' axis; mesh has {tuple(axes)}"
            )
        if "data" not in axes:
            raise ValueError(
                "hierarchical layout needs a 'data' axis for the within-pod "
                f"batch shards; mesh has {tuple(axes)}"
            )
        layout = WorkerLayout(mesh, worker_axes=("pod",), batch_axes=("data",))
    elif style == "single":
        # all devices serve one worker (AR baseline / Lookahead)
        layout = WorkerLayout(
            mesh, worker_axes=(), batch_axes=tuple(a for a in axes if a != "model")
        )
    else:
        raise ValueError(f"unknown layout style {style!r}")
    if spmd:
        validate_spmd_model_axes(layout)
    return layout
