"""fused_nesterov_ms.train: device milliseconds per traced round in the
fused Nesterov kernel of the inner steps (the ``pallas_call`` named
``fused_nesterov``), on device 0 inside the window.  None untraced, or where
no kernel bears the name."""
import scopes


def read(run):
    return scopes.kernel_ms(run, "fused_nesterov")
