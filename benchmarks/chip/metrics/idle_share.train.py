"""idle_share.train: the share of the traced window in which no operation
ran on device 0 (one minus the union of its XLA op intervals)."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share(0)
    return None if share is None else 100.0 * share
