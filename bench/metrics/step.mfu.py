"""Model FLOP utilisation of the whole step: the model FLOPs of the
traced steps (``harness.costs.model_flops_per_token``: non-causal
attention in full, recomputation not counted) over the traced window
times the chips times the chip's bf16 peak (``harness.peaks``)."""
UNIT, LAYER, MOVES = "%", "train step", "tokens_per_s"


def read(r):
    if r.steps <= 0 or r.trace.window_s <= 0:
        return None
    return 100.0 * r.flops_per_step * r.steps / (
        r.trace.window_s * r.chips * r.peak["bf16_flops"])
