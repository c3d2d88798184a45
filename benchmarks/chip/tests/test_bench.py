"""Self-tests of the on-chip benchmark that run on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They drive whole runs at test size with ``run.main(require_chip=False)``:
the look for a TPU is skipped, everything else is a run.  Faults are
planted in the program underneath the timed path, and the run must then
come out not correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, HERE, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import benchtree  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

TRAIN = ("tiny_train", "tiny-olmo-train", "tiny_train_single", 1)
SEED = 3000000019  # more than 32 signed bits hold


def run_cell(tmp_path, cell, trace=0, seed=SEED, extra_files=(), extra_spec=None):
    import run

    bench_path, root = benchtree.build(tmp_path, [cell], extra_files)
    if extra_spec:
        with open(bench_path) as f:
            spec = json.load(f)
        extra_spec(spec)
        with open(bench_path, "w") as f:
            json.dump(spec, f)
    argv = ["--workload", cell[0], "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]
    return run.main(argv, bench_path=bench_path, root=root, require_chip=False)


# -- the harness is driven by data -------------------------------------------


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with BENCHMARK.json entries, run without editing any file."""
    fx = os.path.join(HERE, "fixtures")
    files = [
        ("configs/throwaway-train.json",
         open(os.path.join(fx, "configs", "tiny-olmo-train.json")).read()),
        ("configs/throwaway-train.py",
         open(os.path.join(fx, "configs", "tiny-olmo-train.py")).read()),
        ("traffic/throwaway_mix.json",
         open(os.path.join(fx, "traffic", "tiny_train_single.json")).read()),
        ("metrics/throwaway.tokens.py",
         "def read(run):\n    return run.counts.get('tokens_per_round')\n"),
    ]

    def add_metric(spec):
        spec["per_layer"].append({
            "name": "throwaway.tokens", "unit": "tokens", "better": "higher",
            "source": "program_counter", "layer": "round",
            "moves": "train_tokens_per_s", "workloads": ["throwaway"]})

    cell = ("throwaway", "throwaway-train", "throwaway_mix", 1)
    res = run_cell(tmp_path, cell, trace=1, extra_files=files, extra_spec=add_metric)
    assert res["correct"]
    assert res["metrics"]["throwaway.tokens"]["value"] == 3 * 2 * 64


def test_end_to_end_metrics_of_a_training_run(tmp_path):
    res = run_cell(tmp_path, TRAIN)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_no_tpu_no_result(tmp_path):
    """Off the chip the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "olmo1b_train_1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_same_seed_same_inputs():
    from drivers import train

    markov = {"rank": 8, "temperature": 0.8}
    s1 = np.asarray(train.make_sampler(SEED, 512, 1, markov)(0, 2, 2, 16))
    s2 = np.asarray(train.make_sampler(SEED, 512, 1, markov)(0, 2, 2, 16))
    s3 = np.asarray(train.make_sampler(SEED + 1, 512, 1, markov)(0, 2, 2, 16))
    assert (s1 == s2).all() and len({tuple(r) for r in s1.reshape(-1, 16)}) == 4
    assert s1.shape == s3.shape and (s1 != s3).any()


def test_drawn_rounds_cycle():
    """Set-up draws the rounds' tokens; the window's later rounds reuse
    them in turn, and the checked rounds read rows that all differ."""
    from drivers import train

    sample = train.make_sampler(SEED, 512, 1, {"rank": 8, "temperature": 0.8})
    tokens = train.draw_rounds(sample, 3, 2, 2, 16)
    rounds = [np.asarray(tokens(r, 2, 2, 16)) for r in range(4)]
    assert (rounds[3] == rounds[0]).all()
    assert (rounds[1] == np.asarray(sample(1, 2, 2, 16))).all()
    rows = np.concatenate(rounds[:3]).reshape(-1, 16)
    assert len({tuple(r) for r in rows}) == len(rows)


# -- faults planted under the timed path make the run not correct ------------


def _wrap_round(monkeypatch, wrap):
    from repro.distributed import spmd

    real = spmd.make_spmd_slowmo_round

    def broken(*a, **k):
        return wrap(real(*a, **k))

    monkeypatch.setattr(spmd, "make_spmd_slowmo_round", broken)


def test_fault_state_unchanged(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(round_fn):
        def step(state, batches, lr, *rest):
            _, metrics = round_fn(jax.tree.map(jnp.copy, state), batches, lr, *rest)
            return state, metrics

        return step

    _wrap_round(monkeypatch, wrap)
    res = run_cell(tmp_path, TRAIN)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > 0.99


def test_fault_half_batch(tmp_path, monkeypatch):
    def wrap(round_fn):
        def step(state, batches, lr, *rest):
            t = batches["tokens"]
            half = t.shape[2] // 2
            t = t.at[:, :, half:].set(t[:, :, :half])  # the mean over the first half
            return round_fn(state, {"tokens": t}, lr, *rest)

        return step

    _wrap_round(monkeypatch, wrap)
    res = run_cell(tmp_path, TRAIN)
    assert not res["correct"], res["checks"]


# -- the controls fail -------------------------------------------------------


def test_training_control_and_half_batch_fail(tmp_path):
    """The float8 control, and the reference with half of each worker's
    rows left out, each fail one of the test cell's compared numbers."""
    import types

    import control

    bench_path, root = benchtree.build(tmp_path, [TRAIN])
    cell = harness.load_cell(TRAIN[0], bench_path, root)
    rows = control.faults(cell, types.SimpleNamespace(seed=SEED))
    limits = cell.traffic["limits"]
    for variant in ("control", "half_batch"):
        assert any(rows[variant][k] > limits[k] for k in limits), (variant, rows)


# -- counts and the reference -----------------------------------------------


def test_counts_by_hand():
    olmo = harness.load_module(os.path.join(BENCH, "configs", "olmo-1b-5l-train.py"))
    oc = harness.load_json(os.path.join(BENCH, "configs", "olmo-1b-5l-train.json"))
    # 5 x (4 x 2048^2 + 3 x 2048 x 8192) + 50304 x 2048
    assert olmo.params(oc) == 5 * (4 * 2048**2 + 3 * 2048 * 8192) + 50304 * 2048 == 438566912
    # 3 x (2 x params + 5 layers x 2 x 2048 x (2048 + 1)) = 2.76 GF per token
    assert olmo.train_flops_per_token(oc, 2048) == 3 * (2 * 438566912 + 5 * 2 * 2048 * 2049)


def test_reference_init_is_the_programs():
    import jax

    from reference import dense
    from repro.configs import get_config
    from repro.models import build_model

    cfg = harness.load_json(os.path.join(HERE, "fixtures", "configs", "tiny-olmo-train.json"))
    key = jax.random.PRNGKey(11)
    ours = dense.leaf_names(dense.trunc_normal_init(dense.Arch.from_config(cfg), key))
    theirs = dense.leaf_names(build_model(get_config("olmo-1b", reduced=True)).init(key))
    assert set(ours) == set(theirs)
    for k in ours:
        assert (np.asarray(ours[k]) == np.asarray(theirs[k])).all(), k


# -- the trace reduction -----------------------------------------------------


def test_trace_reduction_on_a_recorded_trace():
    """A TPU v5e trace of two fused-Nesterov calls, two lines 7-8 calls and
    two matrix products, read by hand: 10 XLA ops on device 0 that do not
    overlap, 1.964693 ms busy in all, from 42.540521 ms to 46.001072 ms."""
    t = tracing.Trace(os.path.join(HERE, "data", "small_tpu.xplane.pb"), devices=1)
    assert len(t.ops[0]) == 10
    assert t.busy_s() == pytest.approx(1.964693e-3, abs=1e-9)
    assert t.window_s() == pytest.approx(3.460551e-3, abs=1e-9)
    assert t.idle_share(0) == pytest.approx(1 - 1.964693 / 3.460551, abs=1e-6)
    assert t.exposed_collective_s(0) is None
    b = t.breakdown()
    assert b["device_ops"][0][0] == "%fusion fusion"
    assert b["device_ops"][0][1] == pytest.approx(712252e-9 + 712137e-9)
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_exposed_collective_time():
    E = tracing.Event
    t = tracing.Trace.__new__(tracing.Trace)
    t.lo, t.hi, t.host, t.modules = 0.0, 10.0, [], {}
    t.ops = {0: [
        E("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 1.0, 4.0),
        E("%fusion.2 = f32[8] fusion(f32[8] %y)", 2.0, 3.0),
        E("%all-gather-start = (f32[8]) all-gather-start(f32[8] %z)", 6.0, 7.0),
        E("%fusion.3 = f32[8] fusion(f32[8] %all-reduce.1)", 6.5, 8.0),
    ]}
    # collectives cover [1, 4] and [6, 7]; compute covers [2, 3] and [6.5, 8]
    assert t.exposed_collective_s(0) == pytest.approx(2.0 + 0.5)
    assert t.busy_s() == pytest.approx(3.0 + 2.0)
