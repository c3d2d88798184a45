"""Config-system tests: every assigned architecture's exact spec, the reduced
variants' constraints, and the input-shape table."""
import jax
import pytest

from repro.configs import ARCH_IDS, INPUT_SHAPES, all_configs, get_config
from repro.models import build_model

# (layers, d_model, heads, kv, vocab) from the assignment table
ASSIGNED = {
    "kimi-k2-1t-a32b": (61, 7168, 64, 8, 163840),
    "hubert-xlarge": (48, 1280, 16, 16, 504),
    "xlstm-1.3b": (48, 2048, 4, 4, 50304),
    "qwen3-8b": (36, 4096, 32, 8, 151936),
    "recurrentgemma-2b": (26, 2560, 10, 1, 256000),
    "deepseek-moe-16b": (28, 2048, 16, 16, 102400),
    "qwen2-7b": (28, 3584, 28, 4, 152064),
    "olmo-1b": (16, 2048, 16, 16, 50304),
    "chameleon-34b": (48, 8192, 64, 8, 65536),
    "qwen3-4b": (36, 2560, 32, 8, 151936),
    "deepseek-v2-lite": (27, 2048, 16, 16, 102400),
}


class TestAssignedSpecs:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_exact_dims(self, arch):
        cfg = get_config(arch)
        L, d, h, kv, v = ASSIGNED[arch]
        assert cfg.n_layers == L
        assert cfg.d_model == d
        assert cfg.n_heads == h
        assert cfg.n_kv_heads == kv
        assert cfg.vocab_size == v
        assert cfg.source, "every config must cite its source"

    def test_moe_specs(self):
        k = get_config("kimi-k2-1t-a32b")
        assert (k.n_experts, k.top_k, k.moe_d_ff) == (384, 8, 2048)
        d = get_config("deepseek-moe-16b")
        assert (d.n_experts, d.top_k, d.n_shared_experts) == (64, 6, 2)
        v2 = get_config("deepseek-v2-lite")
        assert (v2.n_experts, v2.top_k, v2.n_shared_experts, v2.moe_d_ff) == (64, 6, 2, 1408)
        assert (v2.kv_lora_rank, v2.qk_nope_head_dim, v2.qk_rope_head_dim,
                v2.v_head_dim) == (512, 128, 64, 128)
        assert not v2.norm_topk_prob and v2.moe_aux == "seq"
        assert (v2.yarn.factor, v2.yarn.original_max_position) == (40.0, 4096)

    def test_feature_flags(self):
        assert get_config("qwen3-8b").qk_norm
        assert get_config("qwen2-7b").qkv_bias
        assert get_config("olmo-1b").norm_type == "nonparam_ln"
        assert not get_config("hubert-xlarge").causal
        assert get_config("recurrentgemma-2b").window == 2048
        assert get_config("recurrentgemma-2b").pattern == ("rec", "rec", "attn")


class TestReducedConstraints:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_reduced_within_limits(self, arch):
        """Assignment: reduced = 2 layers, d_model <= 512, <= 4 experts."""
        cfg = get_config(arch, reduced=True)
        assert cfg.n_layers == 2
        assert cfg.d_model <= 512
        if cfg.n_experts:
            assert cfg.n_experts <= 4
        # family preserved
        assert cfg.family == get_config(arch).family

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_reduced_buildable(self, arch):
        cfg = get_config(arch, reduced=True)
        shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        assert jax.tree.leaves(shapes)


class TestInputShapes:
    def test_table(self):
        t = INPUT_SHAPES
        assert t["train_4k"].seq_len == 4096 and t["train_4k"].global_batch == 256
        assert t["prefill_32k"].seq_len == 32768 and t["prefill_32k"].global_batch == 32
        assert t["decode_32k"].seq_len == 32768 and t["decode_32k"].global_batch == 128
        assert t["long_500k"].seq_len == 524288 and t["long_500k"].global_batch == 1
        assert t["train_4k"].kind == "train"
        assert t["decode_32k"].kind == "decode"

    def test_all_configs_loads_ten(self):
        assert len(all_configs()) == len(ASSIGNED) == 11

    def test_sub_quadratic_flags(self):
        assert get_config("xlstm-1.3b").sub_quadratic
        assert get_config("recurrentgemma-2b").sub_quadratic
        assert not get_config("qwen3-8b").sub_quadratic
        from repro.configs import qwen3_4b

        assert qwen3_4b.LONG_CONTEXT.sub_quadratic  # window variant
