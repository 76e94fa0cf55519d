"""Engine: device time of one decode step program (``gear_decode_step``:
every layer's attention over the paged cache, the token append, the MLPs
and the logits), mean over its runs in the traced window.  The device's
part of ``decode_step_ms``.  Moves ``output_tokens_per_s``."""

from harness import programs

MODULE = "jit_gear_decode_step"


def read(ctx):
    tr = programs.of(ctx)
    runs = [] if tr is None else programs.runs(tr, MODULE)
    if not runs:
        return None
    return 1e-6 * sum(d for _, d in runs) / len(runs)
