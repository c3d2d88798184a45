"""Regression tests for trainer-layer bugfixes: grad_clip wiring, LR-schedule
inner-step units, and checkpoint resume continuity."""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import slowmo
from repro.core.base_opt import InnerOptConfig
from repro.train import checkpoint as ckpt_lib
from repro.train import schedules
from repro.train.trainer import TrainConfig, Trainer, make_lr_fn

W, D = 2, 8


def dummy_model(loss_scale=1.0):
    def init(key):
        return {"w": 0.1 * jax.random.normal(key, (D,))}

    def loss_fn(params, batch):
        pred = batch["tokens"] @ params["w"]
        return loss_scale * jnp.mean((pred - 1.0) ** 2)

    return SimpleNamespace(init=init, loss_fn=loss_fn)


def dummy_sampler(r, tau, B, L):
    key = jax.random.fold_in(jax.random.PRNGKey(7), r)
    return {"tokens": jax.random.normal(key, (tau, W, B, D))}


class TestScheduleValues:
    def test_warmup_step_decay_pinned(self):
        lr = schedules.warmup_step_decay(1.0, 10, (100, 200))
        assert float(lr(4)) == pytest.approx(0.5)
        assert float(lr(9)) == pytest.approx(1.0)
        assert float(lr(50)) == pytest.approx(1.0)
        assert float(lr(150)) == pytest.approx(0.1)
        assert float(lr(250)) == pytest.approx(0.01)

    def test_inverse_sqrt_pinned(self):
        lr = schedules.inverse_sqrt(0.5, 16)
        assert float(lr(7)) == pytest.approx(0.25)  # warmup: 8/16
        assert float(lr(15)) == pytest.approx(0.5)  # peak at warmup end
        assert float(lr(63)) == pytest.approx(0.25)  # (16/64)^0.5

    def test_zero_warmup_means_no_warmup(self):
        """warmup_steps=0 is 'start decaying immediately', not a crash:
        inverse_sqrt used to divide by zero where warmup_step_decay already
        guarded with max(warmup_steps, 1)."""
        inv = schedules.inverse_sqrt(0.5, 0)
        assert float(inv(0)) == pytest.approx(0.5)  # peak at step 1
        assert float(inv(3)) == pytest.approx(0.25)  # (1/4)^0.5
        step = schedules.warmup_step_decay(1.0, 0, (100,))
        assert float(step(0)) == pytest.approx(1.0)
        assert float(step(150)) == pytest.approx(0.1)


class TestLRInnerStepUnits:
    def test_trainer_feeds_inner_steps_not_rounds(self):
        """warmup_steps counts INNER steps: with tau=4 and warmup 8, the
        schedule must reach peak LR at round 2 (step 8), not round 8."""
        tau = 4
        smcfg = slowmo.preset("local_sgd", num_workers=W, tau=tau)
        tc = TrainConfig(
            total_rounds=3, per_worker_batch=2, seq_len=D,
            lr=1.0, schedule="warmup_step", warmup_steps=8, log_every=0,
        )
        t = Trainer(dummy_model(), smcfg, tc, dummy_sampler)
        t.run()
        got = [h["lr"] for h in t.history]
        want = [(0 + 1) / 8, (4 + 1) / 8, 1.0]  # schedule at steps 0, 4, 8
        assert got == pytest.approx(want)

    def test_decay_rounds_convert_to_steps(self):
        """decay_rounds keeps outer-round semantics: milestone 2 means the
        drop happens at inner step 2*tau."""
        lr_fn = make_lr_fn(
            TrainConfig(lr=1.0, schedule="warmup_step", warmup_steps=1,
                        decay_rounds=(2,)),
            tau=4,
        )
        assert float(lr_fn(1 * 4)) == pytest.approx(1.0)  # round 1
        assert float(lr_fn(2 * 4)) == pytest.approx(0.1)  # round 2: dropped


class TestGradClipWiring:
    def test_grad_clip_reaches_inner_opt(self):
        smcfg = slowmo.preset("local_sgd", num_workers=W, tau=1)
        tc = TrainConfig(lr=0.5, grad_clip=1.0)
        t = Trainer(dummy_model(), smcfg, tc, dummy_sampler)
        assert t.smcfg.inner.clip_norm == 1.0

    def test_huge_gradient_step_is_clipped(self):
        """With grad_clip=1 and lr=0.5, a 1e6-scale gradient moves the params
        by at most lr * clip_norm = 0.5 in global norm (the round's exact
        average of per-worker unit directions can only shrink it)."""
        smcfg = dataclasses.replace(
            slowmo.preset("local_sgd", num_workers=W, tau=1),
            inner=InnerOptConfig(kind="sgd", momentum=0.0, nesterov=False),
        )
        tc = TrainConfig(
            total_rounds=1, per_worker_batch=2, seq_len=D,
            lr=0.5, grad_clip=1.0, log_every=0,
        )
        t = Trainer(dummy_model(loss_scale=1e6), smcfg, tc, dummy_sampler)
        state0 = t.init_state()
        w0 = np.asarray(state0.params["w"][0])  # round_fn donates state0
        state1, _ = t.round_fn(state0, t._batches(0), 0.5)
        delta = np.asarray(state1.params["w"][0]) - w0
        assert 0.1 < np.linalg.norm(delta) <= 0.5 * (1 + 1e-4)

    def test_unclipped_for_reference(self):
        smcfg = dataclasses.replace(
            slowmo.preset("local_sgd", num_workers=W, tau=1),
            inner=InnerOptConfig(kind="sgd", momentum=0.0, nesterov=False),
        )
        tc = TrainConfig(total_rounds=1, per_worker_batch=2, seq_len=D,
                         lr=0.5, log_every=0)
        t = Trainer(dummy_model(loss_scale=1e6), smcfg, tc, dummy_sampler)
        state0 = t.init_state()
        w0 = np.asarray(state0.params["w"][0])  # round_fn donates state0
        state1, _ = t.round_fn(state0, t._batches(0), 0.5)
        delta = np.asarray(state1.params["w"][0]) - w0
        assert np.linalg.norm(delta) > 1e3  # the bug this guards against


class TestCheckpointResume:
    def test_resume_matches_uninterrupted(self, tmp_path):
        """save at round 3, restore, run 3 more — losses and LR must equal an
        uninterrupted 6-round run (the LR schedule and sampler continue from
        the absolute round index carried in state.outer_step)."""
        path = str(tmp_path / "ck")
        smcfg = slowmo.preset("local_sgd+slowmo", num_workers=W, tau=2, beta=0.5)
        tc = TrainConfig(
            total_rounds=6, per_worker_batch=2, seq_len=D,
            lr=0.5, schedule="warmup_step", warmup_steps=6, log_every=0,
        )

        t_full = Trainer(dummy_model(), smcfg, tc, dummy_sampler)
        t_full.run()

        t_a = Trainer(dummy_model(), smcfg, tc, dummy_sampler)
        state = t_a.run(rounds=3)
        ckpt_lib.save(path, state, step=3)

        restored, meta = ckpt_lib.restore(path, like=state)
        assert meta["step"] == 3
        assert int(restored.outer_step) == 3
        t_b = Trainer(dummy_model(), smcfg, tc, dummy_sampler)
        t_b.run(state=restored, rounds=3)

        assert [h["round"] for h in t_b.history] == [3, 4, 5]
        full = [(h["loss"], h["lr"]) for h in t_full.history]
        split = [(h["loss"], h["lr"]) for h in t_a.history + t_b.history]
        assert split == pytest.approx(full, rel=1e-6)

    def test_restore_validates_shape_dtype(self, tmp_path):
        path = str(tmp_path / "ck")
        smcfg = slowmo.preset("local_sgd", num_workers=W, tau=1)
        t = Trainer(dummy_model(), smcfg,
                    TrainConfig(total_rounds=1, per_worker_batch=2, seq_len=D,
                                log_every=0),
                    dummy_sampler)
        state = t.init_state()
        ckpt_lib.save(path, state, step=0)
        # valid template passes
        ckpt_lib.restore(path, like=state)
        # mismatched leaf shape is rejected
        bad = state._replace(
            params={"w": jnp.zeros((W, D + 1), jnp.float32)})
        with pytest.raises(ValueError, match="leaf"):
            ckpt_lib.restore(path, like=bad)


class TestHierarchicalBatchSplit:
    """Trainer-side bookkeeping for hierarchical layouts: each worker's
    per-round batch splits over the layout's batch (data) axes, so the
    per-worker batch must divide evenly — checked EAGERLY at Trainer
    construction, not at first jit call."""

    def hier_layout(self, pods=2, data=2):
        from repro.launch.mesh import WorkerLayout

        mesh = SimpleNamespace(
            axis_names=("pod", "data"), shape={"pod": pods, "data": data}
        )
        return WorkerLayout(
            mesh, worker_axes=("pod",), batch_axes=("data",), model_axes=()
        )

    def test_nondivisible_per_worker_batch_rejected(self):
        smcfg = slowmo.preset("local_sgd", num_workers=2, tau=2)
        tc = TrainConfig(total_rounds=1, per_worker_batch=3, seq_len=D, log_every=0)
        with pytest.raises(ValueError, match="divisible"):
            Trainer(dummy_model(), smcfg, tc, dummy_sampler, layout=self.hier_layout())

    def test_divisible_per_worker_batch_accepted(self):
        smcfg = slowmo.preset("local_sgd", num_workers=2, tau=2)
        tc = TrainConfig(total_rounds=1, per_worker_batch=4, seq_len=D, log_every=0)
        t = Trainer(dummy_model(), smcfg, tc, dummy_sampler, layout=self.hier_layout())
        assert t.layout.effective_batch(tc.per_worker_batch) == 8



@pytest.mark.parametrize("packed", [True, False], ids=["packed", "tree"])
def test_launcher_mesh_round_matches_oracle(packed):
    """The launcher's mesh trainer, built from arguments shaped as the chip
    benchmark's (``--mesh host``, one worker), runs the same two rounds as
    the array-axis oracle (``--mesh none``): this is the seam from
    ``launch.train`` through ``Trainer`` to ``spmd.make_spmd_slowmo_round``
    that the chip cells compile through."""
    from repro.configs import get_config
    from repro.core import packing
    from repro.data import MarkovLMConfig, make_markov_sampler
    from repro.launch import train as train_launch

    argv = ["--arch", "olmo-1b", "--algo", "local_sgd+slowmo", "--tau", "2",
            "--seq", "32", "--batch", "2", "--workers", "1"]
    argv += ["--packed"] if packed else []
    vocab = get_config("olmo-1b", reduced=True).vocab_size
    sampler = make_markov_sampler(MarkovLMConfig(vocab_size=vocab, temperature=0.8), 1)
    runs = {}
    for mesh in ("host", "none"):
        trainer = train_launch.build_trainer(
            train_launch.build_parser().parse_args(argv + ["--mesh", mesh]))
        assert (trainer.layout is not None) == (mesh == "host")
        assert (trainer.pack is not None) == packed
        trainer.sampler = sampler
        state = trainer.run(state=trainer.init_state(jax.random.PRNGKey(3)), rounds=2)
        if packed:
            state = packing.unpack_state(trainer.pack, state)
        runs[mesh] = ([h["loss"] for h in trainer.history],
                      jax.tree.map(np.asarray, state.outer_params))
    (mesh_losses, mesh_outer), (ref_losses, ref_outer) = runs["host"], runs["none"]
    np.testing.assert_allclose(mesh_losses, ref_losses, rtol=1e-6)
    assert jax.tree.structure(mesh_outer) == jax.tree.structure(ref_outer)
    for a, b in zip(jax.tree.leaves(mesh_outer), jax.tree.leaves(ref_outer)):
        assert np.max(np.abs(a - b)) <= 1e-6 * max(np.max(np.abs(b)), 1e-12)
