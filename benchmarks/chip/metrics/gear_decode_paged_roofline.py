"""Kernel ``gear_decode_paged``: least time the chip's peaks allow for the
traced decode steps' attention over the compressed history, as a % of the
kernel's device time.  Bytes count only each slot's live chunks (its
compressed tokens), not the capacity the kernel walks, in the layers whose
K/V the GEAR pool holds (``ctx.model.gear_layers``).  Moves
``output_tokens_per_s``."""

from harness import flops, layers

KERNEL = "gear_decode_paged"


def read(ctx):
    f = b = 0
    rows = ctx.slots * ctx.kv_heads
    for _, _, lengths in layers.traced_steps(ctx):
        live = ctx.kv_heads * sum(n // ctx.gear.chunk for n in lengths)
        sf, sb = flops.decode_paged_cost(ctx.gear, live, rows, ctx.group)
        f += ctx.model.gear_layers * sf
        b += ctx.model.gear_layers * sb
    return layers.roofline(ctx, KERNEL, f, b)
