"""Device, whole step: model FLOPs of every token processed in the traced
window (prompt tokens of admissions, one token per active slot per decode
step, attention by context length) over the window and the chip's bf16
peak, in %.  Moves ``output_tokens_per_s``; bounds the kernels' rooflines
of the same cells."""

from harness import layers


def read(ctx):
    return layers.mfu(ctx)
