"""Scheduler: host time between decode steps outside admissions (the
program's ``sched.bookkeeping`` phases: token appends, finishes, slot
resets, admission checks), in ms per decode step of the window, while the
chip waits for the next step.  Moves ``output_tokens_per_s``."""

from harness import programs


def read(ctx):
    gaps = programs.phases(ctx, "sched.bookkeeping")
    steps = programs.phases(ctx, "sched.decode")
    if not gaps or not steps:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in gaps) / len(steps)
