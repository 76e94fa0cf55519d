"""Device time of one GEAR decode kernel call at a served cell's shapes.

    python scripts/time_decode_kernel.py [--kernel PATH ...] [--calls N]

Times the GEAR decode kernels alone, on the chip, at starcoder2-3b's shapes
(2 KV heads of 128, 12 query heads per KV head, 2048 tokens of capacity in
chunks of 64, GEAR-KCVT-4bit: rank 4, 2% outliers), each at two mixes of
per-row extents: every chunk live, and the share of the `decode_2k`
traffic.

- ``gear_decode_paged``, the decode step's attention: 16 slots, 12 query
  rows a row; 352 of the 512 slot-chunks live (68.75%, the traffic's share
  weighted by decode steps).  Dead block-table entries point at page 0.
- ``gear_decode``, streaming prefill's history attention of one 64-token
  chunk: one prompt, 768 query rows (12 heads x 64 tokens) a row, a view of
  32 chunks of which 24 are live (75%; the traffic's share is 76.5%).

Each ``--kernel`` is a ``gear_decode.py`` file loaded on its own (default:
this checkout's), so versions of the kernel are timed in one process on one
chip.  Every file gets the same cache contents, each in its own page
layout: a ``gear_decode_paged`` that takes a ``layout`` reads lane-dense
``codes`` / ``side`` pool leaves (``repro.core.cache.chunks_to_pages``), an
older one the head-flattened per-field pages ``[P*H, ...]``.  Device time
comes from a profiler trace (the benchmark's own reader,
``benchmarks/chip/harness/trace.py``), summed over ``--calls`` calls; the
host clock around ``block_until_ready`` is printed beside it.  One JSON
line per (kernel file, kernel, mix), with the device time of a live grid
step and the largest gap of the outputs to the first file's.  Without a
TPU it exits 1.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from harness import trace as trace_lib  # noqa: E402
from repro.core.cache import (CacheConfig, chunk_field_shapes,  # noqa: E402
                              chunks_to_pages, init_layer_cache, page_layout)
from repro.core.policy import named_policy  # noqa: E402

HEADS, G, DH, CAPACITY = 2, 12, 128, 2048
SLOTS = 16                   # decode batch
PREFILL_ROWS = G * 64        # one prefill chunk's queries per KV head
# live chunks per slot: 352 of 16 x 32
MIXED = (16, 17, 18, 19, 20, 21, 22, 23, 21, 22, 23, 24, 25, 26, 27, 28)
HEAD = ("k_packed", "k_scale", "k_zero", "v_packed", "v_scale", "v_zero")
EXTRA = ("k_a", "k_b", "v_a", "v_b", "k_sp_val", "k_sp_idx", "v_sp_val",
         "v_sp_idx")


def load_kernels(path: str, tag: str):
    spec = importlib.util.spec_from_file_location(f"gear_decode_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.gear_decode_paged, mod.gear_decode


def _random(key, shapes: dict, rows: int, chunk: int):
    """Random operands [rows, ...] for every field of ``shapes``."""
    out = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        full = (rows,) + tuple(shape)
        if name.endswith("_packed"):
            x = jax.random.randint(k, full, -2**31, 2**31 - 1, jnp.int32)
        elif name == "k_sp_idx":
            x = jax.random.randint(k, full, 0, chunk, jnp.int32)
        elif name == "v_sp_idx":
            x = jax.random.randint(k, full, 0, DH, jnp.int32)
        else:
            x = (0.1 * jax.random.normal(k, full)).astype(dtype)
        out[name] = x
    return out


def cache_config():
    return CacheConfig(batch=1, kv_heads=HEADS, head_dim=DH,
                       capacity=CAPACITY, policy=named_policy("gear_kcvt4"))


def page_fields(key):
    """Every slot-chunk's fields [P, H, ...] (page 0 left for dead
    entries), in the dense layout's chunk form."""
    cfg = cache_config()
    return _random(key, chunk_field_shapes(cfg), SLOTS * cfg.n_chunks + 1,
                   cfg.chunk)


def paged_call(fn, fields: dict, q, live, kw: dict):
    """A call of ``fn`` over ``fields`` in ``fn``'s own page layout."""
    cfg = cache_config()
    bt = tables(live, cfg.n_chunks)
    n_slot = jnp.asarray(live, jnp.int32) * cfg.chunk
    if "layout" in inspect.signature(fn).parameters:
        layout = page_layout(cfg)
        pool = chunks_to_pages(layout, fields)
        return lambda: fn(q, pool["codes"], pool["side"], n_slot, bt,
                          layout=layout, scale_factor=kw["scale_factor"])
    flat = {n: x.reshape((-1,) + x.shape[2:]) for n, x in fields.items()}
    return lambda: fn(q, *(flat[n] for n in HEAD), jnp.repeat(n_slot, HEADS),
                      bt, **kw, **{n: flat[n] for n in EXTRA})


def dense_operands(key):
    """One prompt's cache rows [HEADS, ...]."""
    cfg = cache_config()
    cache = jax.eval_shape(lambda: init_layer_cache(cfg))
    shapes = {n: (getattr(cache, n).shape[2:], getattr(cache, n).dtype)
              for n in HEAD + EXTRA}
    return _random(key, shapes, HEADS, cfg.chunk)


def tables(live, n_chunks: int):
    bt = np.zeros((len(live), n_chunks), np.int32)
    for b, n in enumerate(live):
        bt[b, :n] = 1 + b * n_chunks + np.arange(n)
    return jnp.asarray(bt)


def grid_steps(fn, name: str, rows: int, live) -> tuple[int, int]:
    """(grid steps, live grid steps) of one call: the dense kernel and an
    older paged one step per (row, head, chunk), a paged kernel with a
    ``layout`` per (slot, chunk) for all its heads."""
    heads = (1 if name == "gear_decode_paged"
             and "layout" in inspect.signature(fn).parameters else HEADS)
    C = cache_config().n_chunks
    return rows * heads * C, heads * sum(live)


def device_ms(call, calls: int, name: str) -> tuple[float, float, int]:
    """(device ms a call from a trace, host ms a call, calls traced)."""
    jax.block_until_ready(call())                           # compile
    t0 = time.perf_counter()
    for _ in range(calls):
        out = call()
    jax.block_until_ready(out)
    host_ms = (time.perf_counter() - t0) / calls * 1e3
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            out = call()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        red = trace_lib.reduce(trace_lib.load(d), {"k": name})
    n = red["kernel_calls"].get("k", 0)
    return 1e3 * red["kernel_s"].get("k", 0.0) / max(n, 1), host_ms, n


def normalized(out):
    acc, _, l = out
    return np.asarray(acc / jnp.maximum(l[..., :1], 1e-30))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", action="append", default=[],
                    help="a gear_decode.py file (repeatable)")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: kernel times come only from the chip", file=sys.stderr)
        return 1
    paths = args.kernel or [os.path.join(ROOT, "src", "repro", "kernels",
                                         "gear_decode.py")]
    cfg = cache_config()
    C, nb = cfg.n_chunks, cfg.chunk
    key = jax.random.PRNGKey(args.seed)
    fields = page_fields(jax.random.fold_in(key, 1))
    dense = dense_operands(jax.random.fold_in(key, 2))
    q_dec = jax.random.normal(jax.random.fold_in(key, 3), (SLOTS * HEADS, G, DH))
    q_pre = jax.random.normal(jax.random.fold_in(key, 4),
                              (HEADS, PREFILL_ROWS, DH))
    kw = dict(bits=4, chunk=nb, scale_factor=DH**-0.5)
    cases = [("gear_decode_paged", mix, live, SLOTS)
             for mix, live in (("all_live", (C,) * SLOTS), ("mixed", MIXED))]
    cases += [("gear_decode", mix, live, 1)
              for mix, live in (("all_live", (C,)), ("mixed", (24,)))]
    first: dict = {}
    for i, path in enumerate(paths):
        paged_fn, dense_fn = load_kernels(path, str(i))
        for name, mix, live, rows in cases:
            if name == "gear_decode_paged":
                call = paged_call(paged_fn, fields, q_dec, live, kw)
                fn = paged_fn
            else:
                n_comp = jnp.repeat(jnp.asarray(live, jnp.int32) * nb, HEADS)
                call = lambda: dense_fn(  # noqa: E731
                    q_pre, *(dense[n] for n in HEAD), n_comp, **kw,
                    **{n: dense[n] for n in EXTRA})
                fn = dense_fn
            out = normalized(call())
            gap = float(np.abs(out - first.setdefault((name, mix), out)).max())
            dev, host, n = device_ms(call, args.calls, name)
            steps, live_steps = grid_steps(fn, name, rows, live)
            print(json.dumps({
                "kernel_file": path, "kernel": name, "mix": mix,
                "live_share": sum(live) / (rows * C), "calls": n,
                "device_ms_per_call": dev, "host_ms_per_call": host,
                "grid_steps": steps, "live_steps": live_steps,
                "us_per_live_step": 1e3 * dev / live_steps,
                "max_gap_to_first_file": gap,
                "device": jax.devices()[0].device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
