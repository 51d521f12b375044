"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the ``XLA Ops`` intervals / window), in %.  Read in
the open-loop cell, where it moves the inter-token tail."""


def read(run):
    return 100.0 * run.trace.idle_share if run.trace else None
