"""Device time per step of the ops under the program's
``obs::optimizer::flatten`` and ``obs::optimizer::unflatten`` scopes
(``harness.scopes``): the ravel and pad of the parameters and of the
gradient into the flat vector the optimizer works on, and the split of
the updated vector back into parameters, averaged over the chips.  A
fusion that crosses a scope's boundary counts whole under the scope of
the instruction it is named after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "optimizer and compression", "tokens_per_s"


def read(r):
    s = scopes.of_reading(r)
    if not scopes.has_layer_scopes(s):
        return None
    t = (s.get("obs::optimizer::flatten", 0.0)
         + s.get("obs::optimizer::unflatten", 0.0))
    return 1e3 * t / r.steps
