"""slowmo_update_ms.train: device milliseconds per traced round in the
lines 7-8 kernel of the round boundary (the ``pallas_call`` named
``slowmo_update``), on device 0 inside the window.  None untraced, or where
no kernel bears the name."""
import scopes


def read(run):
    return scopes.kernel_ms(run, "slowmo_update")
