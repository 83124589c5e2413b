"""The largest ``peak_bytes_in_use`` over the cell's chips after the
window, in GiB: the memory the step needs, which sets the batch that
fits."""
UNIT, LAYER, MOVES = "GiB", "device", "tokens_per_s"


def read(r):
    if not r.memory_peak_bytes:
        return None
    return r.memory_peak_bytes / 2**30
