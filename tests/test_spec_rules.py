"""Property-style coverage for the model-sharding rule (`model_spec_tail`)
and the one-rule-both-paths invariant of PR 4.

Runs via the ``tests/_hyp.py`` shim (real property tests with hypothesis
installed, clean skips without).  The rule is a pure function from leaf
name/shape to PartitionSpec entries, and both spec paths are pure functions
of a layout's axis bookkeeping, so a duck-typed stand-in mesh keeps all of
this off the single-device test process's jax device state — real meshes
are exercised by the subprocess tests (test_tp_spmd / test_spmd).
"""
import jax
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st

from repro.configs import ARCH_IDS, get_config
from repro.core import slowmo
from repro.distributed import sharding
from repro.launch.mesh import WorkerLayout
from repro.models import build_model


class FakeMesh:
    def __init__(self, axes, sizes):
        self.axis_names = tuple(axes)
        self.shape = dict(zip(axes, sizes))


def tp_layout(pods=2, data=2, model=16):
    mesh = FakeMesh(("pod", "data", "model"), (pods, data, model))
    return WorkerLayout(mesh, worker_axes=("pod",), batch_axes=("data",))


class TestModelSpecTailProps:
    @given(
        d=st.integers(min_value=1, max_value=4096),
        out=st.integers(min_value=1, max_value=4096),
        M=st.sampled_from([2, 4, 8, 16]),
    )
    @settings(max_examples=100, deadline=None)
    def test_divisibility_guard(self, d, out, M):
        """A column-parallel dim shards iff it divides by the model size and
        is at least the model size; nothing else in the leaf ever shards."""
        spec = sharding.model_spec_tail("wq", ("blocks", "attn"), (d, out), M)
        if out % M == 0 and out >= M:
            assert spec == (None, "model")
        else:
            assert spec == (None, None)

    @given(
        stack=st.integers(min_value=0, max_value=3),
        k=st.integers(min_value=1, max_value=64),
        M=st.sampled_from([2, 4, 8, 16]),
    )
    @settings(max_examples=100, deadline=None)
    def test_column_row_duality(self, stack, k, M):
        """Column-parallel leaves (wq/w_in/...) shard their LAST dim, their
        row-parallel partners (wo/w_down/...) the contracting dim -2 —
        regardless of how many leading stack axes the leaf carries."""
        width = k * M
        lead = (7,) * stack
        col = sharding.model_spec_tail("w_in", ("blocks",), lead + (96, width), M)
        row = sharding.model_spec_tail("w_down", ("blocks",), lead + (width, 96), M)
        assert col == (None,) * (stack + 1) + ("model",)
        assert row == (None,) * stack + ("model", None)

    @given(
        shape=st.lists(st.integers(min_value=1, max_value=512), min_size=1, max_size=4),
        name=st.sampled_from(["wq", "wo", "embed", "lm_head", "w_down", "bq", "router"]),
    )
    @settings(max_examples=100, deadline=None)
    def test_model_size_one_replicates_everything(self, shape, name):
        """model_size <= 1 (TP-free layouts) must never emit 'model'."""
        assert sharding.model_spec_tail(name, ("blocks",), tuple(shape), 1) == (
            None,
        ) * len(shape)

    @given(
        d=st.integers(min_value=1, max_value=1024),
        M=st.sampled_from([2, 4, 8]),
    )
    @settings(max_examples=100, deadline=None)
    def test_shard_dims_agree_with_tail(self, d, M):
        """model_shard_dims (the packing path's input) marks exactly the dim
        model_spec_tail marks — one rule feeds both consumers."""
        tree = {
            "wq": jax.ShapeDtypeStruct((96, d), jnp.float32),
            "wo": jax.ShapeDtypeStruct((d, 96), jnp.float32),
            "ln1": jax.ShapeDtypeStruct((96,), jnp.float32),
        }
        dims = sharding.model_shard_dims(tree, M)
        for name, leaf in tree.items():
            tail = sharding.model_spec_tail(name, (), leaf.shape, M)
            want = tail.index("model") if "model" in tail else None
            assert dims[name] == want, (name, tail, dims[name])


class TestPresetSpecUnification:
    """Field-by-field rule (slowmo_state_specs) == mesh rule (spmd_state_specs),
    leaf for leaf, for every architecture preset in configs/ on a
    (pod, data, model=16) layout — the 'one rule, both paths' acceptance."""

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_state_specs_equal_mesh_specs(self, arch):
        cfg = get_config(arch)
        model = build_model(cfg)
        smcfg = slowmo.SlowMoConfig(num_workers=2, tau=2)
        state_shapes = jax.eval_shape(
            lambda k: slowmo.init_slowmo(smcfg, model.init(k)), jax.random.PRNGKey(0)
        )
        lay = tp_layout()
        dry = sharding.slowmo_state_specs(lay, state_shapes)
        mesh = sharding.spmd_state_specs(lay, state_shapes, exact_average=True)
        flat_d, _ = jax.tree_util.tree_flatten_with_path(dry)
        flat_m = jax.tree.leaves(mesh)
        assert len(flat_d) == len(flat_m)
        for (path, a), b in zip(flat_d, flat_m):
            assert a == b, (arch, jax.tree_util.keystr(path), a, b)

    def test_tp_loss_rejects_nondivisible_dims(self):
        """make_tp_loss must reject every dim it treats as sharded that the
        divisibility guard would silently replicate (psumming an already-
        complete value corrupts the math — better an eager error)."""
        from repro.models import tp as tp_lib

        class FakeBackend:
            model_shards = 3

        cfg = get_config("hubert-xlarge", reduced=True)  # 4 heads, d_ff 512
        loss = tp_lib.make_tp_loss(cfg)
        with pytest.raises(ValueError, match="divisible"):
            loss.bind_backend(FakeBackend())
        ok = get_config("hubert-xlarge", reduced=True)
        FakeBackend.model_shards = 2  # 4/512/64 all divide
        assert callable(tp_lib.make_tp_loss(ok).bind_backend(FakeBackend()))

    def test_tp_loss_covers_swiglu_rejects_nondense(self):
        """PR 5: the de-fused swiglu presets bind like any dense config (the
        whole text family is TP-executable); MoE expert parallelism in the
        mapped loss is still a ROADMAP item."""
        from repro.models import tp as tp_lib

        class FakeBackend:
            model_shards = 2

        loss = tp_lib.make_tp_loss(get_config("olmo-1b", reduced=True))
        assert callable(loss.bind_backend(FakeBackend()))
        with pytest.raises(NotImplementedError, match="dense"):
            tp_lib.make_tp_loss(get_config("deepseek-moe-16b", reduced=True))

    def test_batch_specs_model_replicated(self):
        lay = tp_layout()
        spec = sharding.batch_partition_spec(lay, 4)
        assert spec == jax.sharding.PartitionSpec(None, "pod", "data")
        assert "model" not in jax.tree_util.tree_leaves(tuple(spec))
