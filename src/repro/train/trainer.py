"""Training loop: SlowMo rounds over a model bundle + data sampler.

The unit of work is one SlowMo *round* (tau inner steps + outer update), so
the trainer's step counter advances by tau per iteration.  Metrics, LR
scheduling (per outer round, matching the paper's gamma_t), periodic
checkpointing and eval hooks live here.

Boundary variants need no trainer support: ``overlap_boundary`` and
``compress_ratio`` ride the ``SlowMoConfig`` into ``make_slowmo_round``
and their extra state (the double buffer, the error-feedback residual)
rides ``SlowMoState`` through the same checkpoint pack/unpack path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import packing, slowmo
from ..core.slowmo import SlowMoConfig, SlowMoState
from ..models.api import ModelBundle
from . import checkpoint as ckpt_lib
from . import schedules

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    total_rounds: int = 100
    per_worker_batch: int = 8
    seq_len: int = 128
    lr: float = 0.1
    schedule: str = "constant"  # 'constant' | 'warmup_step' | 'inv_sqrt'
    warmup_steps: int = 5  # schedule warmup, in INNER steps
    decay_rounds: tuple[int, ...] = ()  # step-decay milestones, in outer ROUNDS
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_path: str = ""
    grad_clip: float = 0.0  # global-norm clip, wired to InnerOptConfig.clip_norm


class _Program(NamedTuple):
    """What a round runs: the algorithm, the worker layout and the compiled
    round built for them (the elastic loop rebuilds all three)."""

    cfg: SlowMoConfig
    layout: Any
    round_fn: Callable


def _span(phase: str):
    """The host span of one phase of a round, nested in its ``train_round``."""
    return jax.profiler.TraceAnnotation(f"train:{phase}")


def make_lr_fn(tc: TrainConfig, tau: int = 1):
    """LR schedule as a function of the INNER-step index.

    The paper's schedules (Goyal warmup+step-decay, inverse-sqrt) are defined
    in inner steps, so ``warmup_steps`` counts inner steps; the trainer calls
    the schedule with ``round * tau``.  ``decay_rounds`` keeps its outer-round
    semantics and is converted to step milestones here.
    """
    if tc.schedule == "warmup_step":
        decay_steps = tuple(r * tau for r in tc.decay_rounds)
        return schedules.warmup_step_decay(tc.lr, tc.warmup_steps, decay_steps)
    if tc.schedule == "inv_sqrt":
        return schedules.inverse_sqrt(tc.lr, tc.warmup_steps)
    return schedules.constant(tc.lr)


class Trainer:
    def __init__(
        self,
        model: ModelBundle,
        smcfg: SlowMoConfig,
        tc: TrainConfig,
        sampler: Callable[[int, int, int, int], PyTree],
        *,
        eval_fn: Optional[Callable[[PyTree], float]] = None,
        layout=None,
        elastic=None,
        faults=None,
    ):
        if tc.grad_clip and not smcfg.inner.clip_norm:
            smcfg = dataclasses.replace(
                smcfg,
                inner=dataclasses.replace(smcfg.inner, clip_norm=tc.grad_clip),
            )
        if (
            elastic is not None
            and elastic.mask_stragglers
            and smcfg.exact_average
            and not smcfg.masked_average
        ):
            # straggler tolerance: thread the per-round participation mask
            # through the compiled round (a traced input — no recompiles)
            smcfg = dataclasses.replace(smcfg, masked_average=True)
        self.model = model
        self.smcfg = smcfg
        self.tc = tc
        self.sampler = sampler
        self.eval_fn = eval_fn
        self.layout = layout
        self.elastic = elastic
        self.faults = faults
        self.lr_fn = make_lr_fn(tc, smcfg.tau)
        self.pack = None
        if smcfg.packed:
            # flat-buffer execution: the static packing index is derived from
            # the model's parameter SHAPES (no init FLOPs spent here).  On a
            # tensor-parallel layout it is the shard-major ShardedPackSpec,
            # so every device's buffers hold exactly its model shard.
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            self.pack = slowmo.make_state_pack_spec(smcfg, pshapes, layout=layout)
        if layout is not None:
            # mesh-lowered path: worker axis sharded over the layout's mesh,
            # collectives lower to all-reduce / collective-permute.  On a
            # hierarchical layout each worker's per-round batch additionally
            # splits over the batch (data) axes — the sampler still produces
            # (tau, W, per_worker_batch, ...) arrays and shard_map carves the
            # per-device shards, so per_worker_batch must divide evenly.
            shard = getattr(layout, "batch_shard", 1)
            if shard > 1 and tc.per_worker_batch % shard:
                raise ValueError(
                    f"per_worker_batch={tc.per_worker_batch} must be divisible "
                    f"by the {shard}-way batch axes {layout.batch_axes} of the "
                    "hierarchical layout (each worker's batch is split across "
                    "its pod's devices)"
                )
            loss_fn = getattr(model, "loss_with_stats", None) or model.loss_fn
            if getattr(layout, "model_shard", 1) > 1:
                # tensor-parallel workers: the loss must run its matmuls on
                # local model shards with psum over 'model' — swap in the
                # backend-bindable TP loss (same math on a TP-free backend)
                from ..models import tp as tp_lib

                loss_fn = tp_lib.make_tp_loss(model.config)
            self._loss_fn = loss_fn
        else:
            self._loss_fn = getattr(model, "loss_with_stats", None) or model.loss_fn
        self.round_fn = self._build_round(self.smcfg, layout)
        self.history: list[dict] = []

    def _build_round(self, cfg: SlowMoConfig, layout):
        """The compiled round for ``(cfg, layout)`` — also called at elastic
        boundaries to rebuild for a survivor set."""
        if layout is not None:
            from ..distributed import spmd

            return spmd.make_spmd_slowmo_round(
                cfg, self._loss_fn, layout, pack=self.pack
            )
        # the state argument is donated: XLA writes the next round's
        # state into the same buffers (in/out shapes match 1:1), so no
        # per-round full-state copy.  Donation deletes the input state
        # on every backend (CPU included) — run() always rebinds.
        return jax.jit(
            slowmo.make_slowmo_round(cfg, self._loss_fn, pack=self.pack),
            donate_argnums=0,
        )

    def init_state(self, key=None) -> SlowMoState:
        key = jax.random.PRNGKey(0) if key is None else key

        def init(key):
            return slowmo.init_slowmo(self.smcfg, self.model.init(key), pack=self.pack)

        # one compiled init on every path, so a mesh run and the array-axis
        # oracle start from the same floats; on a mesh each device builds its
        # shard in place (the global state holds W copies of the worker
        # state, more than one chip's memory)
        shardings = None
        if self.layout is not None:
            from ..distributed import spmd

            shardings = spmd.state_shardings(
                self.smcfg, self.layout, jax.eval_shape(init, key)
            )
        return jax.jit(init, out_shardings=shardings)(key)

    def _batches(self, round_idx: int) -> PyTree:
        raw = self.sampler(
            round_idx, self.smcfg.tau, self.tc.per_worker_batch, self.tc.seq_len
        )
        if isinstance(raw, dict):
            return raw
        return {"tokens": raw}

    def run(self, state: Optional[SlowMoState] = None, rounds: Optional[int] = None):
        """Run ``rounds`` SlowMo rounds (default: tc.total_rounds).

        Passing a restored ``state`` (e.g. from ``checkpoint.restore``)
        resumes at the round recorded in ``state.outer_step`` — the LR
        schedule and sampler continue from the absolute round index, so a
        resumed run reproduces an uninterrupted one.  Checkpoints always use
        the tree layout; a packed trainer packs a restored tree-layout state
        here and unpacks before saving, so checkpoints are interchangeable
        between execution modes.
        """
        state = state if state is not None else self.init_state()
        if self.pack is not None and not packing.is_packed(state.params):
            state = packing.pack_state(self.pack, jax.tree.map(jnp.asarray, state))
        rounds = rounds if rounds is not None else self.tc.total_rounds
        if self.elastic is not None:
            return self._run_elastic(state, rounds)
        start = int(jax.device_get(state.outer_step))
        # a masked round (cfg.masked_average without the elastic loop) takes
        # the all-ones participation vector — bit-identical to unmasked
        full_mask = (
            (jnp.ones((self.smcfg.num_workers,), jnp.float32),)
            if self.smcfg.masked_average
            else ()
        )

        def dispatch(round_fn, state, batches, lr):
            return round_fn(state, batches, lr, *full_mask)

        prog = _Program(self.smcfg, self.layout, self.round_fn)
        for r in range(start, start + rounds):
            state, prog = self._round(
                r, state, prog, dispatch, last=r == start + rounds - 1
            )
        return state

    def _round(self, r, state, prog, dispatch, *, last, reconfigure=None,
               columns=None, **fields):
        """Round ``r`` of either loop, under its profiler spans: the step
        span ``train_round`` (``step_num=r``) over ``train:reconfigure``
        (elastic only: the state resized and the round rebuilt, so a
        recompile shows here), ``train:sample`` (the batches; ``columns``
        keeps those workers' columns), ``train:dispatch`` (the compiled
        round's call, ``dispatch(round_fn, state, batches, lr)``),
        ``train:sync`` (the metric reads that wait for the device),
        ``train:eval`` and ``train:ckpt``.  ``reconfigure(state, prog)``
        returns the resized state and rebuilt program; ``fields`` join the
        round's history record.  Returns the new state and program."""
        with jax.profiler.StepTraceAnnotation("train_round", step_num=r):
            if reconfigure is not None:
                with _span("reconfigure"):
                    state, prog = reconfigure(state, prog)
            cfg = prog.cfg
            lr = self.lr_fn(r * cfg.tau)
            with _span("sample"):
                batches = self._batches(r)
                if columns is not None:
                    batches = jax.tree.map(
                        lambda x: jnp.take(x, columns, axis=1)
                        if getattr(x, "ndim", 0) >= 2
                        else x,
                        batches,
                    )
            with _span("dispatch"):
                state, metrics = dispatch(prog.round_fn, state, batches, lr)
            with _span("sync"):
                rec = {
                    "round": r,
                    "inner_steps": (r + 1) * cfg.tau,
                    "loss": float(metrics["loss"]),
                    "lr": float(lr),
                    **fields,
                }
                if "drift" in metrics:
                    rec["drift"] = float(metrics["drift"])
                for name, value in metrics.get("stats", {}).items():
                    rec[name] = float(value)
            if self.eval_fn and (r % max(self.tc.log_every, 1) == 0 or last):
                with _span("eval"):
                    rec["eval"] = float(
                        self.eval_fn(_eval_params(cfg, state, self.pack))
                    )
            self.history.append(rec)
            if self.tc.log_every and r % self.tc.log_every == 0:
                extra = "".join(f" {k}={v}" for k, v in fields.items())
                drift = f" drift={rec['drift']:.3e}" if "drift" in rec else ""
                ev = f" eval={rec['eval']:.4f}" if "eval" in rec else ""
                print(
                    f"round {r:4d} step {rec['inner_steps']:6d} "
                    f"loss {rec['loss']:.4f} lr {rec['lr']:.2e}{extra}{drift}{ev}"
                )
            if (
                self.tc.ckpt_every
                and self.tc.ckpt_path
                and (r + 1) % self.tc.ckpt_every == 0
            ):
                with _span("ckpt"):
                    ckpt_lib.save_state(
                        self.tc.ckpt_path, state, step=r + 1, pack=self.pack
                    )
        return state, prog

    def _reconfigure(self, state, prog, *, prev, members):
        """The state and program for a new ordered member set: the state
        sliced to the survivors (evict) or grown from the rebroadcast outer
        state (rejoin), the layout and the round rebuilt for it."""
        from ..elastic import reconfigure

        cfg = dataclasses.replace(prog.cfg, num_workers=len(members))
        if any(w not in prev for w in members):
            # rejoin: survivors keep their slots, new slots fill from the
            # rebroadcast outer state
            state = reconfigure.admit_state(
                cfg, state, prev, members, pack=self.pack
            )
        else:
            # evict: slice the survivor POSITIONS within the previous
            # ordered member list
            keep = [prev.index(w) for w in members]
            state = reconfigure.survivor_state(prog.cfg, state, keep)
        layout = prog.layout
        if layout is not None:
            from ..distributed import spmd as spmd_lib
            from ..launch import mesh as mesh_lib

            layout = mesh_lib.make_survivor_layout(self.layout, members)
            # the reconfigured state still lives on the OLD mesh's devices;
            # commit it to the survivor mesh explicitly
            state = jax.device_put(
                state, spmd_lib.state_shardings(cfg, layout, state)
            )
        return state, _Program(cfg, layout, self._build_round(cfg, layout))

    def _run_elastic(self, state: SlowMoState, rounds: int):
        """The elastic round loop: heartbeats -> evict/rejoin at the
        boundary -> straggler mask -> retried boundary step.

        Membership changes reconfigure BEFORE the round runs: the state is
        sliced (evict) or grown from the rebroadcast outer state (rejoin),
        the layout/round are rebuilt for the ordered survivor set, and the
        survivors' batches are the survivor columns of the full sample —
        so a run that loses worker w reproduces, round for round, a fresh
        survivor-only run seeded from the boundary state (the kill-a-worker
        oracle in tests/test_elastic.py)."""
        from ..elastic import ElasticCoordinator
        from ..elastic.faults import FaultPlan, TransientWorkerError

        plan = self.faults or FaultPlan()
        W0 = self.smcfg.num_workers
        coord = ElasticCoordinator(range(W0), self.elastic)
        prog = _Program(self.smcfg, self.layout, self.round_fn)
        start = int(jax.device_get(state.outer_step))
        for r in range(start, start + rounds):
            # 1. heartbeats, replayed from the fault plan: every member the
            # plan has not killed reports in for round r
            dead = plan.dead(r)
            for w in coord.members:
                if w not in dead:
                    coord.heartbeat(w, r)
            # 2. membership: timeout-based evictions + scheduled rejoins
            prev = coord.members
            coord.advance(r)
            for w in plan.rejoins(r):
                coord.rejoin(w, r)
            members = coord.members
            resize = None
            if members != prev:
                resize = functools.partial(
                    self._reconfigure, prev=prev, members=members
                )
            # 3. this round's participation mask: plan-delayed stragglers
            # plus silent-but-not-yet-evicted workers (detection window)
            extra = ()
            if self.smcfg.masked_average:
                out = plan.delayed(r, self.smcfg.tau) | set(coord.silent(r))
                mvec = np.asarray(
                    [0.0 if w in out else 1.0 for w in members], np.float32
                )
                if not mvec.any():  # never mask every worker out of line 6
                    mvec[:] = 1.0
                extra = (jnp.asarray(mvec),)

            # 4. the boundary step, retried with backoff; injected flaky
            # failures raise BEFORE the donated call, so state is intact
            def dispatch(round_fn, state, batches, lr, extra=extra,
                         fail_n=plan.flaky_attempts(r), r=r):
                def attempt(k):
                    if k < fail_n:
                        raise TransientWorkerError(
                            f"injected boundary failure {k + 1}/{fail_n} at round {r}"
                        )
                    return round_fn(state, batches, lr, *extra)

                return coord.run_boundary(attempt)

            # 5. batches: survivor columns of the full-W sample, so every
            # surviving worker consumes exactly its uninterrupted data stream
            state, prog = self._round(
                r, state, prog, dispatch,
                last=r == start + rounds - 1,
                reconfigure=resize,
                columns=None if members == tuple(range(W0)) else np.asarray(members),
                workers=len(members),
                masked_out=int(len(members) - int(extra[0].sum())) if extra else 0,
            )
        return state


def _eval_params(smcfg: SlowMoConfig, state: SlowMoState, pack=None) -> PyTree:
    """Evaluation parameters: the synchronized outer iterate x_{t,0} (or the
    worker-mean for the noaverage variant), unpacked to the tree layout the
    model's loss/forward functions speak."""
    outer = state.outer_params
    if not smcfg.exact_average:
        outer = jax.tree.map(lambda x: jnp.mean(x, axis=0), outer)
    if pack is not None:
        outer = pack.unpack(outer)
    return outer


def final_loss(history: list[dict]) -> float:
    return history[-1]["loss"] if history else float("nan")


def best_loss(history: list[dict]) -> float:
    return min(h["loss"] for h in history) if history else float("nan")
