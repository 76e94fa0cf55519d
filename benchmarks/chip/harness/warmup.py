"""Set-up: run every program shape the cell's traffic will use, once, so
that nothing compiles inside the measured window.

The served path compiles, per shape:

* one streaming prefill program per prompt band (the engine pads a raw
  prompt up to its band), with an eager pad of the raw prompt per raw length;
* one paged splice program per (closed chunks, reserved zero pages) pair:
  a request reserves the pages of its whole lifetime (prompt + output), so
  the zero-page count varies with the output length inside a band;
* the decode step, the slot reset and the samplers.

Each is driven through the engine's public slot view or the scheduler, at
the shapes the mix can produce and no others.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.serving import Request, Scheduler


def _pages(n: int, chunk: int) -> int:
    return -(-n // chunk)


def zero_page_counts(mix: dict, band: int, chunk: int) -> range:
    """Reserved-but-empty page counts a request of ``band`` can ask for."""
    lo = 1 if mix.get("first_output_from_one") else mix["output"][0]
    hi = mix["output"][1]
    closed = band // chunk - 1
    k_lo = _pages(band - (chunk - 1) + lo - 1, chunk) - closed
    k_hi = _pages(band - 1 + hi - 1, chunk) - closed
    return range(max(k_lo, 1), k_hi + 1)


def warm(engine, mix: dict, chunk: int, vocab: int, log) -> None:
    rng = np.random.default_rng(0)
    bands = [b for b, _ in mix["prompt_bands"]]
    view = engine.new_view()
    n = 0
    for band in bands:
        for raw in range(band - chunk + 1, band):      # eager pad per raw length
            jnp.pad(jnp.zeros((1, raw), jnp.int32), ((0, 0), (0, band - raw)))
        prompt = rng.integers(0, vocab, band - 1, dtype=np.int32)[None]
        closed = band // chunk - 1
        for k in zero_page_counts(mix, band, chunk):
            view.prefill_slot({"tokens": jnp.asarray(prompt)}, 0, admit=False,
                              reserve_tokens=(closed + k) * chunk)
            view.reset_slot(0)
            n += 1
    del view
    # decode step, slot reset and samplers, through the scheduler's own loop:
    # their shapes are the whole batch's, whatever the number of slots in use
    sched = Scheduler(engine)
    for i, band in enumerate(bands):
        sched.submit(Request(rid=-1 - i, tokens=rng.integers(
            0, vocab, band - 1, dtype=np.int32), max_new_tokens=3))
    sched.run_continuous()
    log(f"warm-up: {len(bands)} prefill bands, {n} paged splice shapes, "
        f"{len(bands) * (chunk - 1)} raw lengths, decode")
