"""Unit tests for the sharding rules (no multi-device mesh needed: rules are
pure functions from leaf name/shape to PartitionSpec entries)."""
import jax
import pytest

from repro.analysis.hlo import collective_bytes
from repro.configs import get_config
from repro.distributed import sharding
from repro.models import build_model


class TestModelSpecTail:
    def test_embed_shards_vocab(self):
        assert sharding.model_spec_tail("embed", (), (50304, 2048), 16) == ("model", None)

    def test_nondivisible_replicates(self):
        assert sharding.model_spec_tail("cls_head", (), (1280, 504), 16) == (None, None)

    def test_attention_col_row(self):
        assert sharding.model_spec_tail("wq", ("blocks", "attn"), (16, 2048, 2048), 16) == (
            None, None, "model",
        )
        assert sharding.model_spec_tail("wo", ("blocks", "attn"), (16, 2048, 2048), 16) == (
            None, "model", None,
        )

    def test_moe_expert_dim(self):
        spec = sharding.model_spec_tail("wi", ("moe_blocks",), (27, 64, 2048, 2816), 16)
        assert spec == (None, "model", None, None)

    def test_moe_shared_expert_is_dense_rule(self):
        spec = sharding.model_spec_tail("wi", ("moe_blocks", "shared"), (27, 2048, 5632), 16)
        assert spec == (None, None, "model")

    def test_router_replicated(self):
        assert sharding.model_spec_tail("router", ("moe_blocks",), (27, 2048, 64), 16) == (
            None, None, None,
        )

    def test_norms_replicated(self):
        assert sharding.model_spec_tail("ln1", ("blocks",), (16, 2048), 16) == (None, None)


class TestFullTreeCoverage:
    @pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-moe-16b", "xlstm-1.3b",
                                      "recurrentgemma-2b", "hubert-xlarge"])
    def test_every_leaf_gets_valid_spec(self, arch):
        """Every full-size param leaf maps to a spec whose sharded dims divide."""
        cfg = get_config(arch)
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        M = 16

        def check(path, leaf):
            name, keys = sharding._leaf_name(path)
            spec = sharding.model_spec_tail(name, keys[:-1], leaf.shape, M)
            assert len(spec) == leaf.ndim
            for s, d in zip(spec, leaf.shape):
                if s == "model":
                    assert d % M == 0, (name, leaf.shape, spec)
            return 0

        jax.tree_util.tree_map_with_path(check, shapes)

    def test_big_leaves_actually_sharded(self):
        """All large leaves (>= 8M elements) must be model-sharded, or the
        per-device memory story collapses."""
        cfg = get_config("qwen3-8b")
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        offenders = []

        def check(path, leaf):
            name, keys = sharding._leaf_name(path)
            spec = sharding.model_spec_tail(name, keys[:-1], leaf.shape, 16)
            if leaf.size >= 8_000_000 and "model" not in spec:
                offenders.append((name, leaf.shape))
            return 0

        jax.tree_util.tree_map_with_path(check, shapes)
        assert not offenders, offenders


class TestHloAnalysis:
    def test_collective_parse(self):
        hlo = """
  %x = f32[16,2048]{1,0} all-reduce(f32[16,2048]{1,0} %a), replica_groups={}
  %y = bf16[8,128]{1,0} collective-permute(bf16[8,128]{1,0} %b)
  %z.done = f32[4]{0} all-gather-done(f32[4] %w)
  %t = (f32[4]{0}, f32[8]{0}) all-to-all(f32[4] %c, f32[8] %d)
"""
        out = collective_bytes(hlo)
        assert out["all-reduce"] == 16 * 2048 * 4
        assert out["collective-permute"] == 8 * 128 * 2
        assert out["all-to-all"] == (4 + 8) * 4
        assert out["all-gather"] == 0  # -done carries no new traffic
