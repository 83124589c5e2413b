"""Device time per step of the ops whose source op name runs under
``rematted_computation``: the forward pass that block rematerialisation
recomputes inside the backward pass, every layer scope and ops under
none together, averaged over the chips (``harness.scopes``).  A fusion
that mixes recomputed and backward instructions counts whole on the
side of the instruction it is named after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "model", "tokens_per_s"


def read(r):
    s = scopes.of_reading(r)
    if s is None:
        return None
    t = sum(v for k, v in s.items() if k.endswith(":recompute"))
    return 1e3 * t / r.steps
