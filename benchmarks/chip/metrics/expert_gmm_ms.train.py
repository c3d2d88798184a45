"""expert_gmm_ms.train: device milliseconds per traced round in the grouped
products of the MoE layers' held experts (the ``pallas_call`` named
``expert_gmm``: forward, remat recompute and both backward products), on
device 0 inside the window.  None untraced, or where no kernel bears the
name."""
import scopes


def read(run):
    return scopes.kernel_ms(run, "expert_gmm")
