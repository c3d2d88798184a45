"""Packed flat-buffer execution: layout round-trips, tree-vs-packed round
equivalence across presets/dtypes/W, single-launch outer update, the
block-row padding fix, and checkpoint interchange between layouts.

The mesh-backend half (one all-reduce per boundary, HLO-pinned) lives in
``test_packed_spmd.py`` (subprocess with 8 placeholder devices)."""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing, slowmo
from repro.kernels import ops
from repro.kernels import fused_nesterov as fnk
from repro.kernels import slowmo_update as suk
from repro.train import checkpoint as ckpt_lib
from repro.train.trainer import TrainConfig, Trainer

W, D, B = 8, 16, 4


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_batches(seed, tau, workers=W):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (tau, workers, B, D))
    return {"x": x, "y": jnp.sum(x, -1) * 0.1}


def make_params0(dtype=jnp.float32):
    return {
        "w": jax.random.normal(jax.random.PRNGKey(0), (D,)).astype(dtype),
        "b": jnp.zeros((), dtype),
    }


def assert_states_match(name, tree_state, spec, packed_state, atol=1e-6):
    up = packing.unpack_state(spec, packed_state)
    flat_t, td_t = jax.tree_util.tree_flatten_with_path(tree_state)
    flat_p, td_p = jax.tree.flatten(up)
    assert td_t == td_p, f"{name}: unpacked treedef differs from tree layout"
    for (path, a), m in zip(flat_t, flat_p):
        np.testing.assert_allclose(
            np.asarray(a, np.float32),
            np.asarray(m, np.float32),
            atol=atol,
            rtol=atol,
            err_msg=f"{name}: {jax.tree_util.keystr(path)}",
        )


class TestPackSpec:
    def test_roundtrip_ragged_shapes_and_dtypes(self):
        tree = {
            "a": jnp.arange(5, dtype=jnp.float32),
            "b": jnp.ones((3, 7), jnp.float32),
            "c": jnp.full((), 2.0, jnp.float32),
            "d": jnp.ones((1025,), jnp.bfloat16),  # not divisible by 1024
        }
        spec = packing.make_pack_spec(tree)
        assert set(spec.groups) == {"float32", "bfloat16"}
        p = spec.pack(tree)
        for g in p:
            rows = p[g].shape[-2]
            assert p[g].shape[-1] == packing.LANES
            assert rows % packing.ROW_ALIGN == 0  # block-aligned, no re-pad
        back = spec.unpack(p)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(back[k], np.float32), np.asarray(tree[k], np.float32)
            )

    def test_leading_worker_axis(self):
        tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3), "b": jnp.ones(())}
        spec = packing.make_pack_spec(tree)
        treeW = jax.tree.map(lambda x: jnp.stack([x, 2 * x, 3 * x]), tree)
        p = spec.pack(treeW)
        assert p["float32"].shape == (3, spec.rows("float32"), packing.LANES)
        back = spec.unpack(p)
        np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(treeW["w"]))
        # worker mean over the packed buffer == tree-level worker mean
        mean_p = spec.unpack(jax.tree.map(lambda x: jnp.mean(x, 0), p))
        np.testing.assert_allclose(
            np.asarray(mean_p["w"]), np.asarray(jnp.mean(treeW["w"], 0)), rtol=1e-6
        )

    def test_leaf_view_and_zero_padding(self):
        tree = {"w": jnp.full((5, 7), 3.0), "b": jnp.full((11,), -1.0)}
        spec = packing.make_pack_spec(tree)
        p = spec.pack(tree)
        np.testing.assert_array_equal(
            np.asarray(spec.leaf_view(p, "['b']")), np.asarray(tree["b"])
        )
        # pad region is zero-filled (updates keep it zero for the state's life)
        flat = np.asarray(p["float32"]).reshape(-1)
        assert flat[5 * 7 + 11:].sum() == 0.0

    def test_storage_dtype_override(self):
        tree = {"w": jnp.ones((4,), jnp.bfloat16)}
        spec = packing.make_pack_spec(tree)
        p = spec.pack(jax.tree.map(lambda x: x.astype(jnp.float32), tree),
                      dtype=jnp.float32)
        assert p["bfloat16"].dtype == jnp.float32  # layout label, fp32 storage

    def test_structure_mismatch_raises(self):
        spec = packing.make_pack_spec({"w": jnp.ones((4,))})
        with pytest.raises(ValueError, match="mismatch"):
            spec.pack({"w": jnp.ones((4,)), "extra": jnp.ones((2,))})
        with pytest.raises(ValueError, match="shape"):
            spec.pack({"w": jnp.ones((5,))})

    def test_spec_is_static(self):
        spec = packing.make_pack_spec({"w": jnp.ones((4,))})
        hash(spec)  # closed over by jit -> must be hashable
        assert spec == packing.make_pack_spec({"w": jnp.zeros((4,))})


PRESETS = [
    "local_sgd+slowmo",
    "sgp+slowmo",
    "ar_sgd",
    "sgp+slowmo-noaverage",
    "local_adam+slowmo",
    "dpsgd",
    "osgp",
]


class TestPackedRoundEquivalence:
    @pytest.mark.parametrize("name", PRESETS)
    def test_matches_tree_round(self, name):
        """3 rounds, packed vs per-leaf tree state: every state component and
        the loss metric agree to 1e-6 (same math, different layout)."""
        cfg = slowmo.preset(name, num_workers=W, tau=3)
        pcfg = dataclasses.replace(cfg, packed=True)
        params0 = make_params0()
        spec = slowmo.make_state_pack_spec(pcfg, params0)
        st_t = slowmo.init_slowmo(cfg, params0)
        st_p = slowmo.init_slowmo(pcfg, params0, pack=spec)
        fn_t = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))
        fn_p = jax.jit(slowmo.make_slowmo_round(pcfg, loss_fn, pack=spec))
        for r in range(3):
            b = make_batches(r, cfg.tau)
            st_t, mt = fn_t(st_t, b, 0.1)
            st_p, mp = fn_p(st_p, b, 0.1)
            assert abs(float(mt["loss"]) - float(mp["loss"])) < 1e-6
        assert_states_match(name, st_t, spec, st_p)

    def test_bf16_params(self):
        cfg = slowmo.preset(
            "local_sgd+slowmo", num_workers=W, tau=2, param_dtype=jnp.bfloat16
        )
        pcfg = dataclasses.replace(cfg, packed=True)
        params0 = make_params0()
        spec = slowmo.make_state_pack_spec(pcfg, params0)
        assert spec.groups == ("bfloat16",)
        st_t = slowmo.init_slowmo(cfg, params0)
        st_p = slowmo.init_slowmo(pcfg, params0, pack=spec)
        fn_t = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))
        fn_p = jax.jit(slowmo.make_slowmo_round(pcfg, loss_fn, pack=spec))
        for r in range(2):
            b = make_batches(r, cfg.tau)
            st_t, _ = fn_t(st_t, b, 0.1)
            st_p, _ = fn_p(st_p, b, 0.1)
        assert st_p.params["bfloat16"].dtype == jnp.bfloat16
        assert_states_match("bf16", st_t, spec, st_p)

    def test_bf16_average_dtype_collective(self):
        cfg = slowmo.preset(
            "local_sgd+slowmo", num_workers=W, tau=2, average_dtype=jnp.bfloat16
        )
        pcfg = dataclasses.replace(cfg, packed=True)
        params0 = make_params0()
        spec = slowmo.make_state_pack_spec(pcfg, params0)
        st_t = slowmo.init_slowmo(cfg, params0)
        st_p = slowmo.init_slowmo(pcfg, params0, pack=spec)
        fn_t = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))
        fn_p = jax.jit(slowmo.make_slowmo_round(pcfg, loss_fn, pack=spec))
        b = make_batches(0, cfg.tau)
        st_t, _ = fn_t(st_t, b, 0.1)
        st_p, _ = fn_p(st_p, b, 0.1)
        assert_states_match("bf16-avg", st_t, spec, st_p)

    def test_single_worker(self):
        """W=1 (Lookahead corner): packed buffers keep a size-1 worker axis."""
        cfg = slowmo.preset("lookahead", num_workers=1, tau=3)
        pcfg = dataclasses.replace(cfg, packed=True)
        params0 = make_params0()
        spec = slowmo.make_state_pack_spec(pcfg, params0)
        st_t = slowmo.init_slowmo(cfg, params0)
        st_p = slowmo.init_slowmo(pcfg, params0, pack=spec)
        fn_t = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))
        fn_p = jax.jit(slowmo.make_slowmo_round(pcfg, loss_fn, pack=spec))
        for r in range(2):
            b = make_batches(r, cfg.tau, workers=1)
            st_t, _ = fn_t(st_t, b, 0.1)
            st_p, _ = fn_p(st_p, b, 0.1)
        assert_states_match("W=1", st_t, spec, st_p)

    def test_packed_requires_spec(self):
        cfg = dataclasses.replace(
            slowmo.preset("local_sgd+slowmo", num_workers=W), packed=True
        )
        with pytest.raises(ValueError, match="PackSpec"):
            slowmo.make_slowmo_round(cfg, loss_fn)


class TestPackedPallasLaunches:
    def _count_launches(self, monkeypatch):
        calls = {"outer": 0, "nesterov": 0}
        orig_su, orig_fn = suk.slowmo_update_2d, fnk.fused_nesterov_2d

        def su_counted(*a, **k):
            calls["outer"] += 1
            return orig_su(*a, **k)

        def fn_counted(*a, **k):
            calls["nesterov"] += 1
            return orig_fn(*a, **k)

        monkeypatch.setattr(suk, "slowmo_update_2d", su_counted)
        monkeypatch.setattr(fnk, "fused_nesterov_2d", fn_counted)
        return calls

    def test_one_outer_launch_per_boundary(self, monkeypatch):
        """Packed + use_pallas: ONE outer-update kernel launch per round
        (vs one per leaf on the tree layout) — and the two modes still agree
        numerically.  The ``local`` base runs its communication-free inner
        loop on the tree layout (boundary-only packing), so the fused inner
        kernel is per-leaf there by design."""
        calls = self._count_launches(monkeypatch)
        params0 = make_params0()  # 2 leaves
        cfg = dataclasses.replace(
            slowmo.preset("local_sgd+slowmo", num_workers=W, tau=2), use_pallas=True
        )
        pcfg = dataclasses.replace(cfg, packed=True)
        spec = slowmo.make_state_pack_spec(pcfg, params0)
        b = make_batches(0, cfg.tau)

        st_p = slowmo.init_slowmo(pcfg, params0, pack=spec)
        st_p, _ = jax.jit(slowmo.make_slowmo_round(pcfg, loss_fn, pack=spec))(
            st_p, b, 0.1
        )
        packed_calls = dict(calls)
        calls.update(outer=0, nesterov=0)

        st_t = slowmo.init_slowmo(cfg, params0)
        st_t, _ = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))(st_t, b, 0.1)
        tree_calls = dict(calls)

        assert packed_calls == {"outer": 1, "nesterov": 2}  # boundary packed
        assert tree_calls == {"outer": 2, "nesterov": 2}  # one per leaf
        assert_states_match("pallas", st_t, spec, st_p, atol=1e-6)

    def test_packed_inner_single_fused_launch(self, monkeypatch):
        """Bases that communicate every step (AR) run the inner loop fully
        packed: the fused Nesterov update is ONE launch over the whole
        momentum buffer, not one per leaf."""
        calls = self._count_launches(monkeypatch)
        params0 = make_params0()  # 2 leaves
        cfg = dataclasses.replace(
            slowmo.preset("ar_sgd", num_workers=W), use_pallas=True, packed=True
        )
        spec = slowmo.make_state_pack_spec(cfg, params0)
        st = slowmo.init_slowmo(cfg, params0, pack=spec)
        b = make_batches(0, cfg.tau)
        st, _ = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn, pack=spec))(st, b, 0.1)
        assert calls == {"outer": 1, "nesterov": 1}

        tree_cfg = dataclasses.replace(cfg, packed=False)
        calls.update(outer=0, nesterov=0)
        st_t = slowmo.init_slowmo(tree_cfg, params0)
        st_t, _ = jax.jit(slowmo.make_slowmo_round(tree_cfg, loss_fn))(st_t, b, 0.1)
        assert calls == {"outer": 2, "nesterov": 2}
        assert_states_match("ar-pallas", st_t, spec, st, atol=1e-6)


class TestBlockRowPadding:
    def test_sub_tile_leaves_no_longer_pad_to_full_tile(self):
        """A 300k-element leaf used to round up to a full 256-row tile
        (512 rows); block sizes are now picked from the PADDED row count
        with waste bounded by max(7 rows, 12.5%) — here 64-row blocks with
        27 rows of pad instead of 219."""
        x = jnp.zeros((300_000,))
        raw_rows = -(-x.size // ops.LANES)  # 293
        t = ops._tiling(x.shape)
        x2d = ops._to_2d(x, t)
        assert not t.copy_free and t.block_rows == 64
        assert x2d.shape[0] % t.block_rows == 0
        assert x2d.shape[0] - raw_rows <= max(7, raw_rows // 8)  # was 219 rows

    def test_large_leaves_keep_large_blocks(self):
        """Near-tile-aligned big leaves must not degrade to 8-row blocks:
        the relative-waste rule keeps 256-row tiles when the pad is <1%."""
        assert ops._tiling((25144 * ops.LANES,)).block_rows == 256  # rows % 64 != 0
        # and packed buffers (64-row aligned) always divide exactly
        assert ops._tiling((64, ops.LANES)).block_rows == 64
        assert ops._tiling((512, ops.LANES)).block_rows == 256
        assert ops._tiling((3, 192, ops.LANES)).block_rows == 64

    @pytest.mark.parametrize("size", [3, 1024, 5000, 8 * 1024, 293 * 1024, 2**18])
    def test_pick_divides_padded_rows(self, size):
        x = jnp.zeros((size,))
        t = ops._tiling(x.shape)
        x2d = ops._to_2d(x, t)
        assert x2d.shape == ((x2d.size // ops.LANES), ops.LANES) == (t.rows, t.cols)
        assert x2d.shape[0] % t.block_rows == 0
        assert ops._from_2d(x2d, t, x.shape).shape == x.shape

    def test_aligned_buffer_is_not_copied(self):
        """Packed buffers ((rows, LANES), rows % block == 0) take the reshape
        fast path — the returned 2D view has exactly the input's elements."""
        x = jnp.arange(8 * ops.LANES, dtype=jnp.float32).reshape(8, ops.LANES)
        t = ops._tiling(x.shape)
        assert t.copy_free and ops._to_2d(x, t).shape == (8, ops.LANES)
        # and a worker-stacked packed buffer flattens without padding
        xw = jnp.stack([x, x])
        t = ops._tiling(xw.shape)
        assert t.copy_free and ops._to_2d(xw, t).shape == (16, ops.LANES)


def dummy_model():
    def init(key):
        return {"w": 0.1 * jax.random.normal(key, (D,)), "b": jnp.zeros(())}

    def fwd(params, batch):
        pred = batch["tokens"] @ params["w"] + params["b"]
        return jnp.mean((pred - 1.0) ** 2)

    return SimpleNamespace(init=init, loss_fn=fwd)


def dummy_sampler(r, tau, Bc, L):
    key = jax.random.fold_in(jax.random.PRNGKey(7), r)
    return {"tokens": jax.random.normal(key, (tau, W, Bc, D))}


class TestCheckpointInterchange:
    def _trainer(self, packed):
        smcfg = slowmo.preset(
            "local_sgd+slowmo", num_workers=W, tau=2, beta=0.5, packed=packed
        )
        tc = TrainConfig(
            total_rounds=6, per_worker_batch=2, seq_len=D,
            lr=0.5, schedule="warmup_step", warmup_steps=6, log_every=0,
        )
        return Trainer(dummy_model(), smcfg, tc, dummy_sampler)

    def test_packed_resume_matches_uninterrupted(self, tmp_path):
        """Packed run -> tree-layout checkpoint -> packed resume reproduces
        the uninterrupted packed run (donated state included)."""
        path = str(tmp_path / "ck")
        t_full = self._trainer(packed=True)
        t_full.run()

        t_a = self._trainer(packed=True)
        state = t_a.run(rounds=3)
        ckpt_lib.save_state(path, state, step=3, pack=t_a.pack)

        t_b = self._trainer(packed=True)
        template = packing.unpack_state(t_b.pack, t_b.init_state())
        restored, meta = ckpt_lib.restore_state(path, like=template, pack=t_b.pack)
        assert meta["step"] == 3 and int(restored.outer_step) == 3
        assert packing.is_packed(restored.params)
        t_b.run(state=restored, rounds=3)

        full = [(h["loss"], h["lr"]) for h in t_full.history]
        split = [(h["loss"], h["lr"]) for h in t_a.history + t_b.history]
        assert split == pytest.approx(full, rel=1e-6)

    def test_cross_mode_interchange(self, tmp_path):
        """A checkpoint written by a packed run restores byte-identically
        into a per-leaf trainer (and the packed trainer accepts the
        tree-layout state directly via run())."""
        path = str(tmp_path / "ck")
        t_p = self._trainer(packed=True)
        state_p = t_p.run(rounds=2)
        ckpt_lib.save_state(path, state_p, step=2, pack=t_p.pack)

        t_t = self._trainer(packed=False)
        restored, _ = ckpt_lib.restore(path, like=t_t.init_state())
        restored = jax.tree.map(jnp.asarray, restored)
        t_t.run(state=restored, rounds=2)

        # and the tree-layout state feeds a PACKED trainer unconverted
        restored2, _ = ckpt_lib.restore(
            path, like=packing.unpack_state(t_p.pack, t_p.init_state())
        )
        t_p2 = self._trainer(packed=True)
        t_p2.run(state=jax.tree.map(jnp.asarray, restored2), rounds=2)
        losses_t = [h["loss"] for h in t_t.history]
        losses_p = [h["loss"] for h in t_p2.history]
        assert losses_t == pytest.approx(losses_p, rel=1e-6, abs=1e-7)
