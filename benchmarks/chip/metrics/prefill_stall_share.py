"""Scheduler: share of the window's host time spent in admissions (batch-1
``prefill_slot`` and the first token's sample), which every decoding slot
waits through.  Moves ``itl_p95_ms``."""

from harness import layers


def read(ctx):
    busy = sum(t1 - t0 for t0, t1, _, _ in layers.admissions(ctx))
    return 100.0 * busy / layers.host_window_s(ctx)
