"""Device time per step of the non-collective ops after the gradient:
its flattening, the 1-bit Adam update, 1-bit compress and decompress
and error feedback, averaged over the chips."""
UNIT, LAYER, MOVES = "ms", "optimizer and compression", "tokens_per_s"


def read(r):
    t = r.trace.class_s.get("optimizer", 0.0)
    return 1e3 * t / r.steps if t > 0 and r.steps > 0 else None
