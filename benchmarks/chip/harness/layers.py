"""What the per-layer readers share: the window's host records with the
profiler's stop stall taken out, and the records inside the traced part.

A reader (``metrics/<name>.py``) has ``read(ctx) -> float | None`` and
returns None when its cell gives it nothing to read.  ``ctx`` carries
``hub`` (the window's records, ``harness.window.Hub``), ``lo``/``hi`` (the
window), ``traced`` (host interval of the trace), ``stall`` (host interval
of ``stop_trace``), ``trace`` (``harness.trace.reduce`` of the trace),
``peaks`` (``peaks.json`` of the device, None off the chip), ``model``
(the configuration's flop model, ``flop_model(cfg)`` of its architecture
module: ``token_flops``, ``prefill_flops``, ``gear_layers``), ``gear``
(``flops.Gear``), ``slots``, ``kv_heads``, ``group`` (query heads per KV
head).
"""

from __future__ import annotations


def _overlap(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def host_window_s(ctx) -> float:
    """Window length without the profiler's stop stall."""
    return (ctx.hi - ctx.lo) - _overlap(*ctx.stall, ctx.lo, ctx.hi)


def clean(ctx, a: float, b: float) -> bool:
    """Interval inside the window and clear of the stop stall."""
    return a >= ctx.lo and b <= ctx.hi and _overlap(a, b, *ctx.stall) == 0.0


def steps(ctx):
    """Decode steps (end, host seconds, cache lengths) in the window."""
    return [s for s in ctx.hub.steps if clean(ctx, s[0] - s[1], s[0])]


def admissions(ctx):
    """Admissions (start, first token, rid, prompt tokens) in the window."""
    return [a for a in ctx.hub.admissions if clean(ctx, a[0], a[1])]


def traced_steps(ctx):
    t0, t1 = ctx.traced
    return [s for s in ctx.hub.steps if t0 < s[0] <= t1]


def traced_admissions(ctx):
    t0, t1 = ctx.traced
    return [a for a in ctx.hub.admissions if t0 < a[1] <= t1]


def roofline(ctx, key: str, flops: float, nbytes: float) -> float | None:
    """Least time the peaks allow for (flops, nbytes), as a % of the
    kernel's device time in the trace; None without a chip or the kernel."""
    t = ctx.trace["kernel_s"].get(key, 0.0)
    if ctx.peaks is None or t <= 0.0 or nbytes <= 0:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def mfu(ctx) -> float | None:
    """Model FLOPs of the traced window's tokens over window x peak, in %."""
    w = ctx.trace["window_s"]
    if ctx.peaks is None or w <= 0:
        return None
    m = ctx.model
    f = sum(m.prefill_flops(n) for *_, n in traced_admissions(ctx))
    f += sum(m.token_flops(n, logits=True)
             for _, _, lengths in traced_steps(ctx) for n in lengths)
    return 100.0 * f / (w * ctx.peaks["bf16_flops_per_s"])
