"""Compressed boundary (``SlowMoConfig.compress_ratio``) — docs §7.

Pins the DeMo-style top-k + error-feedback protocol end to end:

* config guards (exact-average only, ratio in (0, 1]); dense configs carry
  no ``residual`` leaves (checkpoints/donation untouched);
* the shared ``payload_spec`` arithmetic — 64Ki-element blocking, floor-k
  (the acceptance point: values+indices bytes <= 0.2x dense at ratio 0.1),
  and the oracle sparsify/reconstruct semantics;
* ``sparsify_batch`` keeps exactly the k largest magnitudes of every
  (slot, block) on packed-shaped signals, losslessly at ratio 1.0;
* ``compress_ratio=1.0`` is DENSE-equivalent to 1e-6 — tree and packed,
  blocking and overlapped — with an exactly-zero residual;
* the residual rides checkpoints (pack -> save -> restore -> unpack) and
  elastic surgery (sliced on evict, kept by survivors on admit, zeroed
  for joiners);
* mesh census + numerics (subprocess, 8 host devices): the packed
  compressed round issues exactly TWO sparse all-gathers sized by
  ``payload_spec`` with the dense boundary all-reduce GONE, and matches
  the axis oracle leaf-exactly;
* the audit sweep is clean under ``--compressed both`` while the
  ``dense-boundary`` mutation fails (subprocess);
* the ratio sweep stays under the ``repro.analysis.compress_drift`` bound.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import compress_drift
from repro.core import packing, slowmo
from repro.elastic import reconfigure
from repro.kernels import topk_compress
from repro.train import checkpoint as ckpt_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

W, D, B, TAU = 4, 16, 4, 3


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_params():
    return {
        "w": 0.3 * jax.random.normal(jax.random.PRNGKey(0), (D, D)),
        "b": jnp.zeros((D,)),
    }


def make_batches(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (TAU, W, B, D))
    return {"x": x, "y": jnp.sum(x, -1, keepdims=True) * 0.1}


def compress_cfg(ratio=1.0, **overrides):
    return dataclasses.replace(
        slowmo.preset("local_sgd+slowmo", num_workers=W, tau=TAU),
        compress_ratio=ratio,
        **overrides,
    )


def assert_tree_close(a, b, atol=1e-6, msg=""):
    for (path, x), y in zip(
        jax.tree_util.tree_flatten_with_path(a)[0], jax.tree.leaves(b)
    ):
        np.testing.assert_allclose(
            np.asarray(x, np.float32),
            np.asarray(y, np.float32),
            atol=atol,
            rtol=1e-6,
            err_msg=f"{msg}{jax.tree_util.keystr(path)}",
        )


class TestConfigAndState:
    def test_requires_exact_average(self):
        with pytest.raises(ValueError, match="compress_ratio"):
            dataclasses.replace(
                slowmo.preset("sgp+slowmo-noaverage", num_workers=W),
                compress_ratio=0.5,
            )

    @pytest.mark.parametrize("ratio", [0.0, -0.1, 1.5])
    def test_ratio_range(self, ratio):
        with pytest.raises(ValueError, match="in \\(0, 1\\]"):
            compress_cfg(ratio)

    def test_dense_state_has_no_residual_leaves(self):
        cfg = slowmo.preset("local_sgd+slowmo", num_workers=W, tau=TAU)
        st = slowmo.init_slowmo(cfg, make_params())
        assert st.residual is None
        assert len(jax.tree.leaves(st.residual)) == 0

    def test_compressed_state_residual_zero_like_params(self):
        cfg = compress_cfg(0.5)
        st = slowmo.init_slowmo(cfg, make_params())
        assert st.residual is not None
        for (path, r), p in zip(
            jax.tree_util.tree_flatten_with_path(st.residual)[0],
            jax.tree.leaves(st.params),
        ):
            assert r.shape == p.shape, jax.tree_util.keystr(path)
            assert r.dtype == jnp.float32
            assert not np.asarray(r).any()


class TestPayloadSpec:
    def test_blocked_when_multiple_of_block(self):
        n = 4 * topk_compress.BLOCK_ELEMS
        blocks, be, k = topk_compress.payload_spec(n, 0.25)
        assert (blocks, be) == (4, topk_compress.BLOCK_ELEMS)
        assert k == topk_compress.BLOCK_ELEMS // 4

    def test_single_block_otherwise(self):
        blocks, be, k = topk_compress.payload_spec(100, 0.5)
        assert (blocks, be, k) == (1, 100, 50)
        # k floors but never hits zero
        assert topk_compress.payload_spec(3, 0.1)[2] == 1

    def test_floor_k_meets_payload_acceptance_bound(self):
        """values(f32) + indices(s32) bytes <= 0.2x dense f32 at ratio 0.1
        — the FLOOR in k is load-bearing (ceil would give 0.20002x)."""
        for n in (topk_compress.BLOCK_ELEMS, 8 * topk_compress.BLOCK_ELEMS):
            blocks, be, k = topk_compress.payload_spec(n, 0.1)
            payload = blocks * k * (4 + 4)
            assert payload <= 0.2 * n * 4, (n, k, payload)

    @pytest.mark.parametrize("n,ratio", [(0, 0.5), (10, 0.0), (10, 1.2)])
    def test_validation(self, n, ratio):
        with pytest.raises(ValueError):
            topk_compress.payload_spec(n, ratio)

    def test_oracle_selects_by_magnitude(self):
        flat = jnp.asarray([[1.0, -7.0, 0.5, 3.0, -2.0, 0.0, 6.0, -0.1]])
        vals, idx = topk_compress.sparsify_ref(flat, 3)
        dense = topk_compress.reconstruct(vals[None], idx[None], 8)[0, 0]
        np.testing.assert_array_equal(
            np.asarray(dense),
            np.asarray([0.0, -7.0, 0.0, 3.0, 0.0, 0.0, 6.0, 0.0]),
        )


class TestSelection:
    def test_sparsify_batch_keeps_top_magnitudes_per_block(self):
        """Each (slot, block) keeps exactly k entries, and no dropped entry
        outweighs a kept one: blocks are selected independently."""
        L, blocks = 3, 2
        x = jax.random.normal(
            jax.random.PRNGKey(4), (L, blocks * topk_compress.BLOCK_ELEMS)
        )
        vals, idx, spec = topk_compress.sparsify_batch(x, 0.25)
        assert spec == (blocks, topk_compress.BLOCK_ELEMS,
                        topk_compress.BLOCK_ELEMS // 4)
        assert vals.shape == idx.shape == (L, blocks, spec[2])
        dense = np.asarray(topk_compress.reconstruct(vals, idx, spec[1]))
        xb = np.asarray(x).reshape(L, blocks, -1)
        kept = dense != 0
        assert (kept.sum(-1) == spec[2]).all()
        np.testing.assert_array_equal(dense[kept], xb[kept])
        mag = np.abs(xb)
        lo_kept = np.where(kept, mag, np.inf).min(-1)
        hi_dropped = np.where(kept, -np.inf, mag).max(-1)
        assert (lo_kept >= hi_dropped).all()

    def test_sparsify_batch_ratio_one_is_lossless(self):
        x = jax.random.normal(
            jax.random.PRNGKey(5), (2, topk_compress.BLOCK_ELEMS)
        )
        vals, idx, spec = topk_compress.sparsify_batch(x, 1.0)
        dense = topk_compress.reconstruct(vals, idx, spec[1])
        np.testing.assert_array_equal(
            np.asarray(dense).reshape(x.shape), np.asarray(x)
        )


class TestDenseEquivalence:
    @pytest.mark.parametrize("packed", [False, True], ids=["tree", "packed"])
    @pytest.mark.parametrize("overlap", [False, True], ids=["blocking", "overlap"])
    def test_ratio_one_equals_dense(self, packed, overlap):
        """ratio=1.0 keeps every entry: the sparse protocol must reproduce
        the dense round to 1e-6 with an exactly-zero residual."""
        params0 = make_params()
        cfg_d = dataclasses.replace(
            slowmo.preset("local_sgd+slowmo", num_workers=W, tau=TAU),
            packed=packed,
            overlap_boundary=overlap,
        )
        cfg_c = dataclasses.replace(cfg_d, compress_ratio=1.0)
        pack = (
            slowmo.make_state_pack_spec(cfg_d, params0) if packed else None
        )
        st_d = slowmo.init_slowmo(cfg_d, params0, pack=pack)
        st_c = slowmo.init_slowmo(cfg_c, params0, pack=pack)
        fn_d = jax.jit(slowmo.make_slowmo_round(cfg_d, loss_fn, pack=pack))
        fn_c = jax.jit(slowmo.make_slowmo_round(cfg_c, loss_fn, pack=pack))
        for r in range(3):
            b = make_batches(r)
            st_d, met_d = fn_d(st_d, b, 0.1)
            st_c, met_c = fn_c(st_c, b, 0.1)
        assert_tree_close(st_c.outer_params, st_d.outer_params, msg="outer ")
        assert_tree_close(st_c.params, st_d.params, msg="params ")
        assert_tree_close(st_c.slow_u, st_d.slow_u, msg="slow_u ")
        assert float(met_c["loss"]) == pytest.approx(float(met_d["loss"]), abs=1e-6)
        resid = sum(
            float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(st_c.residual)
        )
        assert resid == 0.0

    def test_lossy_ratio_runs_and_feeds_back(self):
        cfg = compress_cfg(0.1)
        st = slowmo.init_slowmo(cfg, make_params())
        fn = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn))
        for r in range(2):
            st, met = fn(st, make_batches(r), 0.1)
        assert np.isfinite(float(met["loss"]))
        resid = sum(
            float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(st.residual)
        )
        assert resid > 0.0  # something was withheld — error feedback is live


class TestCheckpointAndElastic:
    def test_residual_packs_and_checkpoints(self, tmp_path):
        params0 = make_params()
        cfg = compress_cfg(0.25, packed=True)
        pack = slowmo.make_state_pack_spec(cfg, params0)
        st = slowmo.init_slowmo(cfg, params0, pack=pack)
        st, _ = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn, pack=pack))(
            st, make_batches(0), 0.1
        )
        path = str(tmp_path / "ckpt")
        ckpt_lib.save_state(path, st, step=1, pack=pack)
        tree_template = packing.unpack_state(pack, st)
        restored, meta = ckpt_lib.restore_state(
            path, like=tree_template, pack=pack
        )
        assert int(meta["step"]) == 1
        assert_tree_close(restored.residual, st.residual, msg="residual ")
        assert_tree_close(restored.outer_params, st.outer_params, msg="outer ")

    def test_unpack_pack_residual_round_trip(self):
        params0 = make_params()
        cfg = compress_cfg(0.25, packed=True)
        pack = slowmo.make_state_pack_spec(cfg, params0)
        st = slowmo.init_slowmo(cfg, params0, pack=pack)
        tree_st = packing.unpack_state(pack, st)
        assert tree_st.residual is not None
        back = packing.pack_state(pack, tree_st)
        assert_tree_close(back.residual, st.residual, msg="residual ")

    def test_evict_slices_residual(self):
        cfg = compress_cfg(0.25)
        st = slowmo.init_slowmo(cfg, make_params())
        marked = st._replace(
            residual=jax.tree.map(
                lambda x: x
                + jnp.arange(W, dtype=jnp.float32).reshape(
                    (W,) + (1,) * (x.ndim - 1)
                ),
                st.residual,
            )
        )
        surv = reconfigure.survivor_state(cfg, marked, [0, 2, 3])
        for leaf in jax.tree.leaves(surv.residual):
            assert leaf.shape[0] == 3
            np.testing.assert_array_equal(
                np.asarray(leaf)[:, ...].reshape(3, -1)[:, 0], [0.0, 2.0, 3.0]
            )

    def test_admit_keeps_survivor_residual_zeroes_joiner(self):
        cfg3 = dataclasses.replace(compress_cfg(0.25), num_workers=3)
        st3 = slowmo.init_slowmo(cfg3, make_params())
        marked = st3._replace(
            residual=jax.tree.map(lambda x: x + 7.0, st3.residual)
        )
        cfg4 = dataclasses.replace(cfg3, num_workers=4)
        grown = reconfigure.admit_state(cfg4, marked, [0, 1, 2], [0, 1, 2, 9])
        for leaf in jax.tree.leaves(grown.residual):
            flat = np.asarray(leaf).reshape(4, -1)
            assert (flat[:3] == 7.0).all()  # survivors keep error feedback
            assert (flat[3] == 0.0).all()  # joiner starts clean


class TestDrift:
    def test_ratio_sweep_within_pinned_bound(self):
        worst = 0.0
        for ratio in compress_drift.DEFAULT_RATIOS:
            rec = compress_drift.measure_drift(ratio=ratio)
            worst = max(worst, rec["outer_rel_drift"])
            if ratio == 1.0:  # exact reconstruction: platform-noise drift only
                assert rec["outer_rel_drift"] < 1e-5, rec
        assert worst <= compress_drift.DEFAULT_BOUND, worst


# ---------------------------------------------------------------------------
# subprocess: mesh backend + audit CLI (both force multi-device host
# platforms, which must never leak into this pytest process — conftest)
# ---------------------------------------------------------------------------
def _run(script_or_args):
    if isinstance(script_or_args, str):
        argv = [sys.executable, "-c", script_or_args]
    else:
        argv = [sys.executable] + script_or_args
    return subprocess.run(
        argv,
        capture_output=True,
        text=True,
        timeout=900,
        env={
            "PYTHONPATH": os.path.join(REPO_ROOT, "src"),
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "JAX_PLATFORMS": "cpu",
        },
        cwd=REPO_ROOT,
    )


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np

from repro.analysis import hlo
from repro.core import slowmo
from repro.distributed import spmd
from repro.kernels import topk_compress
from repro.launch.mesh import make_spmd_layout

W, D, B, RATIO = 8, 32, 4, 0.25

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)

def make_batches(seed, tau):
    x = jax.random.normal(jax.random.PRNGKey(seed), (tau, W, B, D))
    return {"x": x, "y": jnp.sum(x, -1, keepdims=True) * 0.1}

cfg = dataclasses.replace(
    slowmo.preset("local_sgd+slowmo", num_workers=W, tau=3),
    packed=True,
    compress_ratio=RATIO,
)
params0 = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(0), (D, D)),
           "b": jnp.zeros((D,))}
layout = make_spmd_layout(W)
pack = slowmo.make_state_pack_spec(cfg, params0, layout=layout)
state_a = slowmo.init_slowmo(cfg, params0, pack=pack)
state_m = jax.tree.map(jnp.array, state_a)  # fn_m donates its state
fn_a = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn, pack=pack))
fn_m = spmd.make_spmd_slowmo_round(cfg, loss_fn, layout, pack=pack)

b0 = make_batches(0, cfg.tau)
lowered = fn_m.build(state_m, b0).lower(state_m, b0, jnp.float32(0.1))
ops = hlo.collective_ops(hlo.lowered_hlo_text(lowered))
ags = [op for op in ops if op["op"] == "all-gather"]
ars = [op for op in ops if op["op"] == "all-reduce"]
# the packed state is ONE f32 group of 64 rows -> one 64Ki-element unit
rows = sum(r for _, r in pack.group_rows)
blocks, be, k = topk_compress.payload_spec(rows * 1024, RATIO)
payload = W * blocks * k * 4  # all-gather RESULT bytes, per payload field
assert sorted(op["bytes"] for op in ags) == [payload, payload], (
    [op["bytes"] for op in ags], payload)
# the dense boundary all-reduce is GONE: only the 4-byte loss pmean remains
assert [op["bytes"] for op in ars] == [4], [op["bytes"] for op in ars]

for r in range(3):
    b = make_batches(r, cfg.tau)
    state_a, met_a = fn_a(state_a, b, 0.1)
    state_m, met_m = fn_m(state_m, b, 0.1)
flat_a, _ = jax.tree_util.tree_flatten_with_path(state_a)
flat_m = jax.tree.leaves(state_m)
assert len(flat_a) == len(flat_m)
for (path, a), m in zip(flat_a, flat_m):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(m, np.float32),
        atol=1e-6, rtol=1e-6, err_msg=jax.tree_util.keystr(path))
print("MESH-COMPRESS-OK")
"""


def test_mesh_compress_census_and_oracle_equivalence():
    proc = _run(MESH_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH-COMPRESS-OK" in proc.stdout


def test_audit_compressed_clean():
    proc = _run(
        [
            "-m",
            "repro.analysis.audit",
            "--presets",
            "local_sgd+slowmo",
            "--layouts",
            "flat",
            "--packed",
            "both",
            "--compressed",
            "both",
            "--overlap",
            "both",
        ]
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "0 violation(s)" in proc.stdout


def test_audit_dense_boundary_mutation_must_fail():
    proc = _run(
        [
            "-m",
            "repro.analysis.audit",
            "--presets",
            "local_sgd+slowmo",
            "--layouts",
            "flat",
            "--packed",
            "packed",
            "--compressed",
            "compressed",
            "--mutate",
            "dense-boundary",
        ]
    )
    assert proc.returncode != 0, proc.stdout[-3000:]
    assert "FAIL" in proc.stdout
