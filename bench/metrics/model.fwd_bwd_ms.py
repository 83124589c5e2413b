"""Device time per step of the ops of the gradient computation (source
op names holding ``jvp(``: the forward and backward passes, recomputed
ops included), averaged over the chips."""
UNIT, LAYER, MOVES = "ms", "model", "tokens_per_s"


def read(r):
    t = r.trace.class_s.get("model", 0.0)
    return 1e3 * t / r.steps if t > 0 and r.steps > 0 else None
