"""Device time per step of the non-collective ops under the program's
``obs::exchange::server`` scope (``harness.scopes``): each chip's work
as the server of its chunk of the momentum -- the average of the
worker chunks it received, and the compression of that average with
the server's error feedback -- averaged over the chips.  On one chip
the chunk is the whole vector, on four a quarter of it.  None where
the program names no such scope.  A fusion that crosses the scope's
boundary counts whole under the scope of the instruction it is named
after."""
from harness import scopes

UNIT, LAYER, MOVES = "ms", "exchange", "tokens_per_s"
SERVER = "obs::exchange::server"


def read(r):
    s = scopes.of_reading(r)
    if not s or SERVER not in s:
        return None
    return 1e3 * s[SERVER] / r.steps
