"""Engine: host time of the window's decode steps (dispatch, device step,
sample, blocking read) over their number.  Moves ``output_tokens_per_s``."""

from harness import layers


def read(ctx):
    st = layers.steps(ctx)
    return None if not st else 1e3 * sum(s for _, s, _ in st) / len(st)
