"""The program's REDUCED deepseek-v2-lite (2 layers of width 128, 4 experts,
all held), trained by SlowMo: the test-sized twin of
deepseek-v2-lite-5l-train, with the same reference and counts."""
from __future__ import annotations

import os

import harness

_full = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "deepseek-v2-lite-5l-train.py")
)
program_sizes = _full.program_sizes
params = _full.params
train_flops_per_token = _full.train_flops_per_token
expert_gmm_flops_per_round = _full.expert_gmm_flops_per_round
reference_train = _full.reference_train
