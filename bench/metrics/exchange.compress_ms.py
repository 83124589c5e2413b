"""Device time per step of the non-collective ops under the program's
``obs::exchange::<compressor>`` scopes (``harness.scopes``): the worker's
compression of the momentum with its error feedback, the average of
the received chunks, the server's re-compression with its error
feedback and the decompression, averaged over the chips.  The
collectives are left out.  A fusion that crosses a scope's boundary
counts whole under the scope of the instruction it is named after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "exchange", "tokens_per_s"


def read(r):
    s = scopes.of_reading(r)
    if not scopes.has_layer_scopes(s):
        return None
    t = sum(v for k, v in s.items() if k.startswith("obs::exchange::")
            and not k.endswith((":recompute", ":collective")))
    return 1e3 * t / r.steps
