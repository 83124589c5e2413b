"""Device time per step of the exchange's collectives: the union of the
spans of the collective ops under an ``obs::exchange::<part>`` scope,
or made by the compiler with no source name, on the ``XLA Ops`` and
``Async XLA Ops`` lines, averaged over the chips
(``harness.exchange.collective_s``).  None where no such collective ran
(one chip)."""
from harness import exchange

UNIT, LAYER, MOVES = "ms", "exchange", "tokens_per_s"


def read(r):
    found = exchange.kept(r)
    if found is None or found[1] <= 0:
        return None
    return 1e3 * found[1] / r.steps
