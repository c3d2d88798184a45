"""flash_attention_ms.train: device milliseconds per traced round in the
flash attention kernels of the training round (the ``pallas_call`` named
``flash_attention``: forward, remat recompute, and the dq and dk/dv
backward kernels), on device 0 inside the window.  None untraced, or where
no kernel bears the name."""
import scopes


def read(run):
    return scopes.kernel_ms(run, "flash_attention")
