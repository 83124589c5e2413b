"""The exchange's collectives' share of their interconnect roofline:
the least time of the bytes one chip sends in one step's 1-bit exchange
(``harness.wire.onebit_send_bytes``: the all-to-all's and the
all-gather's packed signs and block scales) at the chip's interconnect
peak (``harness.peaks``, ``ici_bits_per_s``), over the measured
``exchange.collective_ms``.  Bytes bound the collectives; only those a
chip sends are counted."""
from harness import exchange, wire

UNIT, LAYER, MOVES = "%", "exchange", "tokens_per_s"


def read(r):
    found = exchange.kept(r)
    if found is None or found[1] <= 0:
        return None
    sizes = exchange.layout(found[0])
    if sizes is None or sizes[2] < 2:
        return None
    least_s = 8 * wire.onebit_send_bytes(*sizes) / r.peak["ici_bits_per_s"]
    return 100.0 * least_s / (found[1] / r.steps)
