"""Kernel ``gear_decode_paged``: least time the chip's peaks allow for the
traced decode steps' attention over the compressed history, as a % of the
kernel's device time.  Bytes count only each slot's live chunks (its
compressed tokens), not the capacity the kernel walks.  Moves
``output_tokens_per_s``."""

from harness import flops, layers

KERNEL = "gear_decode_paged"


def read(ctx):
    f = b = 0
    rows = ctx.slots * ctx.kv_heads
    for _, _, lengths in layers.traced_steps(ctx):
        live = ctx.kv_heads * sum(n // ctx.gear.chunk for n in lengths)
        sf, sb = flops.decode_paged_cost(ctx.gear, live, rows, ctx.group)
        f += ctx.model.layers * sf
        b += ctx.model.layers * sb
    return layers.roofline(ctx, KERNEL, f, b)
