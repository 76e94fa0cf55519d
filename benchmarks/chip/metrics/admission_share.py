"""Scheduler: share of the window's host time inside the program's own
``sched.admission`` phases (queue pop to the first token on the host: the
batch-1 prefill, the numeric guard, the paged splice, the first sample),
which every decoding slot waits through, over the window less the
profiler's stop stall.  The program's twin of ``prefill_stall_share``,
which the harness times around the same calls.  Moves ``itl_p95_ms``."""

from harness import layers, programs


def read(ctx):
    ph = programs.phases(ctx, "sched.admission")
    if ph is None:
        return None
    return 100.0 * sum(t1 - t0 for t0, t1 in ph) / layers.host_window_s(ctx)
