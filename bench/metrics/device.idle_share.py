"""Share of the traced window in which no op ran on the chip, averaged
over the chips: 1 - (union of ``XLA Ops`` events) / window."""
UNIT, LAYER, MOVES = "%", "device", "tokens_per_s"


def read(r):
    if r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
