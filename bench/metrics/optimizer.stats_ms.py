"""Device time per step of the ops under the program's
``obs::optimizer::stats`` scope (``harness.scopes``): the per-step
diagnostics the step returns beside the loss (the variance's L1 norm,
the gradient, momentum and both error-feedback norms) and their
reduction over the mesh, averaged over the chips.  A norm that the
compiler fuses into another pass counts under the scope of the
instruction that fusion is named after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "optimizer and compression", "tokens_per_s"


def read(r):
    s = scopes.of_reading(r)
    if not scopes.has_layer_scopes(s):
        return None
    return 1e3 * s.get("obs::optimizer::stats", 0.0) / r.steps
