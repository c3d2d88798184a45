"""Shared benchmark harness: CPU-scale analogs of the paper's experiments.

The paper's tasks (ResNet/CIFAR, ResNet/ImageNet, Transformer/WMT) are
GPU-cluster scale; the CPU container runs the same *optimization comparison*
on a small transformer LM over a synthetic Markov-chain corpus (learnable,
with a known entropy floor).  What must reproduce is the ORDERING and the
qualitative effects (SlowMo improves each base optimizer; tau has an interior
optimum; alpha=1 best; buffer strategies behave as in App. B.4) — not the
absolute numbers, which are task-specific.

Results are cached under artifacts/bench3/ as JSON; `benchmarks.run`
aggregates and prints the final CSV.  Only losses are recorded: these runs
compare optimizers, and speed is measured on the chip (`benchmarks/chip/`).
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import slowmo
from repro.core.base_opt import InnerOptConfig
from repro.data import MarkovLMConfig, chain_entropy, make_markov_sampler
from repro.models import build_model

CACHE_DIR = "artifacts/bench3"

# benchmark task: small-but-real transformer on a learnable Markov LM.
# REGIME NOTE: the budget/LR put the comparison in the TRANSIENT regime
# (none of the methods has reached the task's entropy floor yet) — that is
# where optimizer quality discriminates, mirroring the paper's fixed-epoch
# budgets.  artifacts/bench/ (first pass, 600 steps @ lr 0.25) showed the
# saturated regime: every method at the floor, differences pure noise — kept
# as a negative control.
VOCAB = 64
SEQ = 64
PER_WORKER_BATCH = 4
NUM_WORKERS = 8
ROUNDS_PER_TAU12 = 20  # budget in INNER STEPS: tau * rounds is held constant
TOTAL_INNER_STEPS = 12 * ROUNDS_PER_TAU12
DEFAULT_LR = 0.05


def bench_model(seed: int = 0):
    cfg = (
        get_config("olmo-1b", reduced=True)
        .replace(vocab_size=VOCAB, n_layers=2, d_model=128, d_ff=256, n_heads=4, n_kv_heads=4)
    )
    return build_model(cfg)


def data_cfg():
    return MarkovLMConfig(vocab_size=VOCAB, temperature=0.7, heterogeneity=0.0)


@dataclasses.dataclass
class RunResult:
    name: str
    final_loss: float
    best_loss: float
    eval_loss: float
    history: list

    def as_dict(self):
        return dataclasses.asdict(self)


def run_algorithm(
    name: str,
    smcfg: slowmo.SlowMoConfig,
    *,
    lr: float = DEFAULT_LR,
    total_inner_steps: int = TOTAL_INNER_STEPS,
    seed: int = 0,
    cache_key: str | None = None,
) -> RunResult:
    cache_key = cache_key or name
    path = os.path.join(CACHE_DIR, f"{cache_key}.json")
    if os.path.exists(path):
        with open(path) as f:
            d = json.load(f)
        return RunResult(**d)

    model = bench_model()
    sampler = make_markov_sampler(data_cfg(), smcfg.num_workers)
    round_fn = jax.jit(slowmo.make_slowmo_round(smcfg, model.loss_fn))
    params = model.init(jax.random.PRNGKey(seed))
    state = slowmo.init_slowmo(smcfg, params)

    rounds = max(1, total_inner_steps // smcfg.tau)
    history = []
    for r in range(rounds):
        batch = {"tokens": sampler(r, smcfg.tau, PER_WORKER_BATCH, SEQ)}
        state, metrics = round_fn(state, batch, lr)
        history.append(float(metrics["loss"]))

    # held-out eval on the synchronized parameters
    eval_params = state.outer_params
    if not smcfg.exact_average:
        eval_params = jax.tree.map(lambda x: jnp.mean(x, axis=0), eval_params)
    eval_params = jax.tree.map(lambda x: x.astype(jnp.float32), eval_params)
    eval_batch = {"tokens": sampler(10_000, 1, 64, SEQ)[0, 0]}
    eval_loss = float(jax.jit(model.loss_fn)(eval_params, eval_batch))

    res = RunResult(
        name=name,
        final_loss=float(np.mean(history[-5:])),
        best_loss=float(np.min(history)),
        eval_loss=eval_loss,
        history=history,
    )
    os.makedirs(CACHE_DIR, exist_ok=True)
    with open(path, "w") as f:
        json.dump(res.as_dict(), f)
    return res


def preset_cfg(preset: str, tau: int = 12, beta: float = 0.6, **kw) -> slowmo.SlowMoConfig:
    return slowmo.preset(
        preset,
        num_workers=NUM_WORKERS,
        tau=tau,
        beta=beta,
        inner=InnerOptConfig(kind="sgd", momentum=0.9, nesterov=True, weight_decay=1e-4),
        **kw,
    )


# ---------------------------------------------------------------------------
# analytic communication model (Table 2 analog): bytes per inner iteration
# per worker, N = parameter count: a ring all-reduce moves 2N per member, a
# gossip push N; SlowMo's boundary all-reduce amortizes over tau steps.
# ---------------------------------------------------------------------------

def comm_bytes_per_step(name: str, n_params: int, tau: int, dtype_bytes: int = 2) -> float:
    N = n_params * dtype_bytes
    ring_allreduce = 2 * N  # 2N per member (reduce-scatter + all-gather)
    gossip = N  # send one copy to one peer
    table = {
        "ar": ring_allreduce,
        "local": ring_allreduce / tau,
        "local+slowmo": ring_allreduce / tau,  # SlowMo adds NO communication here
        "sgp": gossip,
        "sgp+slowmo": gossip + ring_allreduce / tau,
        "sgp+slowmo-noaverage": gossip,  # §6: boundary allreduce removed
        "double_averaging": 2 * ring_allreduce / tau,  # params + momentum buffers
    }
    return table[name]


def floor_entropy() -> float:
    return chain_entropy(data_cfg())
