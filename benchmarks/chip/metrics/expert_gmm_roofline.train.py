"""expert_gmm_roofline.train: the ``expert_gmm`` kernel's share of the chip's
bf16 peak: its operations per round over its device time per round.  The
operations are the configuration's ``expert_gmm_flops_per_round``: every
call a round makes (the forward, its remat recompute and both backward
products of each forward product) at the balanced routed count, so padding
rows and uneven routing count as time and not as work.  None untraced, off
the chip, or where no kernel bears the name or the configuration gives no
count."""
import scopes


def read(run):
    ms = scopes.kernel_ms(run, "expert_gmm")
    count = getattr(run.cell.config_mod, "expert_gmm_flops_per_round", None)
    if ms is None or not ms or count is None or run.peaks is None:
        return None
    flops = count(run.cell.config, run.cell.traffic)
    return 100.0 * flops / (ms * 1e-3 * run.peaks["bf16_flops_per_s"])
