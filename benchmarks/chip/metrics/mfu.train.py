"""mfu.train: the training step's share of the chips' bf16 peak over the
traced window: forward and backward operations per token (from the
configuration's count, recomputation left out) times the tokens of the
rounds traced, over the traced window's length, the chips and the peak."""


def read(run):
    t, c = run.trace, run.counts
    if t is None or run.peaks is None or not c.get("rounds_traced") or t.window_s() <= 0:
        return None
    flops = c["flops_per_token"] * c["tokens_per_round"] * c["rounds_traced"]
    return 100.0 * flops / (t.window_s() * run.chips * run.peaks["bf16_flops_per_s"])
