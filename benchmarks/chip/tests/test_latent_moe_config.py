"""Self-tests of the DeepSeek-V2-Lite configuration that run on the CPU: its
sizes, parameters and operation counts against the program's model, the
reference's initial tree against the program's, and a whole run of its
test-sized twin.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import os
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, HERE, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import benchtree  # noqa: E402
import harness  # noqa: E402

NAME = "deepseek-v2-lite-5l-train"
TINY = ("tiny_dsv2_train", "tiny-dsv2-train", "tiny_train_single", 1)
SEED = 3000000019


def _config():
    mod = harness.load_module(os.path.join(BENCH, "configs", NAME + ".py"))
    return mod, harness.load_json(os.path.join(BENCH, "configs", NAME + ".json"))


def _program(config):
    from repro.launch import train as train_launch

    args = train_launch.build_parser().parse_args(config["program_args"])
    from repro.configs import get_config

    cfg = get_config(args.arch, reduced=not args.full).replace(
        n_layers=args.layers, experts_held=args.experts_held, vocab_size=args.vocab)
    return cfg


def test_sizes_params_and_counts_match_the_program():
    import jax

    from repro.models import build_model, param_count

    mod, config = _config()
    cfg = _program(config)
    harness.check_sizes(mod.program_sizes(config), cfg)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert mod.params(config) == param_count(shapes) == 535_060_992
    # by hand: the dense layer 81.0M, each MoE layer 100.4M, embedding and
    # head 2 x 12800 x 2048
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert attn == 13_762_560
    tokens_routed = 6 * 8 / 64 * 3 * 2048 * 1408
    matmul = (attn + 3 * 2048 * 10944) + 4 * (
        attn + 2048 * 64 + tokens_routed + 3 * 2048 * 2816) + 2048 * 12800
    attn_ops = 5 * 16 * (192 + 128) * 4097
    assert mod.train_flops_per_token(config, 4096) == 3 * (2 * matmul + attn_ops)
    traffic = harness.load_json(os.path.join(BENCH, "traffic", "train_seq4096_single.json"))
    rows = 2 * 4096 * 6 * 8 / 64  # 6144 held rows a step
    assert mod.expert_gmm_flops_per_round(config, traffic) == 12 * 4 * 24 * rows * 2048 * 1408


def test_reference_init_is_the_programs():
    import jax

    from reference import dense, latent_moe
    from repro.configs import get_config
    from repro.models import build_model

    cfg = harness.load_json(os.path.join(HERE, "fixtures", "configs", "tiny-dsv2-train.json"))
    key = jax.random.PRNGKey(11)
    arch = latent_moe.Arch.from_config(cfg)
    ours = dense.leaf_names(latent_moe.trunc_normal_init(arch, key))
    prog = get_config("deepseek-v2-lite", reduced=True)
    theirs = dense.leaf_names(build_model(prog).init(key))
    assert set(ours) == set(theirs)
    for k in ours:
        assert (np.asarray(ours[k]) == np.asarray(theirs[k])).all(), k


def test_tiny_twin_runs_correct_and_its_controls_fail(tmp_path):
    import control
    import run

    bench_path, root = benchtree.build(tmp_path, [TINY])
    argv = ["--workload", TINY[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"]
    result = run.main(argv, bench_path=bench_path, root=root, require_chip=False)
    assert result["correct"], result["checks"]
    cell = harness.load_cell(TINY[0], bench_path, root)
    rows = control.faults(cell, types.SimpleNamespace(seed=SEED))
    limits = cell.traffic["limits"]
    for variant in ("control", "half_batch"):
        assert any(rows[variant][k] > limits[k] for k in limits), (variant, rows)
