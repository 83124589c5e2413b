"""Device time per step of the ops under the program's
``obs::model::attention`` scope (``harness.scopes``): the q/k/v
projections, rotary embedding, scores, softmax, the probability-value
product and the output projection, in the forward pass, the backward
pass and the recomputed forward, averaged over the chips.  A fusion
that crosses the scope's boundary counts whole under the scope of the
instruction it is named after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "model", "tokens_per_s"


def read(r):
    s = scopes.of_reading(r)
    if not scopes.has_layer_scopes(s):
        return None
    return 1e3 * s.get("obs::model::attention", 0.0) / r.steps
