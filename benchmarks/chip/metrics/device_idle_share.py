"""Device: share of the traced window in which no operation ran on the
chip.  Moves ``output_tokens_per_s``."""


def read(ctx):
    w = ctx.trace["window_s"]
    return None if w <= 0 else 100.0 * (1.0 - ctx.trace["busy_s"] / w)
