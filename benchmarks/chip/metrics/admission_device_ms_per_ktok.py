"""Prefill: device time of the programs an admission runs (the batch-1
streaming prefill ``gear_prefill*``, the numeric guard
``gear_finite_guard`` and the paged splice ``gear_paged_splice``) inside
the ``sched.admission`` phases of the traced window, in ms per 1,000
prompt tokens of those admissions.  Moves ``itl_p95_ms``."""

from harness import programs

MODULES = ("jit_gear_prefill", "jit_gear_finite_guard", "jit_gear_paged_splice")


def read(ctx):
    tr = programs.of(ctx)
    if tr is None:
        return None
    ns = tokens = 0
    for s, d, args in programs.annotations(tr, "sched.admission"):
        ns += sum(dd for _, dd in programs.runs(tr, MODULES, s, s + d))
        tokens += int(args.get("prompt_tokens", 0))
    if not ns or not tokens:
        return None
    return 1e-3 * ns / tokens
