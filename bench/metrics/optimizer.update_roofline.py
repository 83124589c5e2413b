"""The optimizer's share of its HBM roofline: the least bytes a
compressed 1-bit Adam step needs per worker
(``harness.costs.onebit_adam_least_bytes``) over the chip's HBM
bandwidth, divided by the measured ``optimizer.update_ms``.  The update
does a few FLOPs per byte, so bandwidth bounds it."""
UNIT, LAYER, MOVES = "%", "optimizer and compression", "tokens_per_s"


def read(r):
    t = r.trace.class_s.get("optimizer", 0.0)
    if t <= 0 or r.steps <= 0:
        return None
    least_s = r.optimizer_least_bytes / r.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (t / r.steps)
