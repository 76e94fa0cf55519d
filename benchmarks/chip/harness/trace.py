"""From a profiler trace to numbers: device busy time, kernel time by name,
the top device operations, and idle gaps labelled by the host spans around
them.

``load`` turns the profiler's ``.xplane.pb`` into a small JSON-able dict
(the only form ``reduce`` reads, and the form the recorded test trace is
kept in):

    {"device": [[start_ns, dur_ns, op, text], ...],   # one chip's ops
     "host":   [[start_ns, dur_ns, name], ...]}       # annotated spans

``op`` is the HLO instruction's name (``gear_decode_paged.6``: a Pallas
kernel's custom call takes the name of the jitted function that wraps it),
``text`` the instruction and every string statistic the profiler gave it.
A kernel's time is the time of the ops whose name, less its ``.N`` suffix,
is the kernel's name.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

# host spans that label idle gaps: the engine's annotations and the
# harness's own
HOST_SPANS = ("gear.prefill", "gear.prefill_suffix", "gear.decode",
              "bench.admission", "bench.wait_arrival", "bench.bookkeeping")
WINDOW_SPAN = "bench.traced"     # the harness's span over the traced window
OPS_LINE = "XLA Ops"
# control-flow ops whose events enclose their bodies' ops: left out of the
# top ops, which would count that time twice
CONTAINERS = ("while", "conditional", "call")


def load(trace_dir: str, device: str = "/device:TPU:0") -> dict:
    """The trace under ``trace_dir`` in ``reduce``'s form.  Device ops are
    the ``XLA Ops`` line of the ``device`` plane; on the CPU backend, which
    has no device plane, the host events that carry an ``hlo_op``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    dev, host = [], []
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    stats = " ".join(str(v) for _, v in e.stats
                                     if isinstance(v, str))
                    dev.append([int(e.start_ns), int(e.duration_ns),
                                op_name(e.name), f"{e.name} {stats}"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS or e.name == WINDOW_SPAN:
                        host.append([int(e.start_ns), int(e.duration_ns),
                                     e.name])
                    elif not device.startswith("/device:TPU"):
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            dev.append([int(e.start_ns), int(e.duration_ns),
                                        op_name(e.name),
                                        f"{e.name} {st.get('hlo_module', '')}"])
    dev.sort()
    host.sort()
    return {"device": dev, "host": host}


def op_name(event_name: str) -> str:
    """``%name.6 = f32[...] custom-call(...)`` -> ``name.6``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _merged(events, lo: int, hi: int) -> list[list[int]]:
    """Union of the events' intervals, clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, d, *_ in events:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _op_group(name: str) -> str:
    """An op's name less its ``.N`` suffix: ``fusion.410`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name) or name


def reduce(tr: dict, kernels: dict[str, str]) -> dict:
    """Numbers of one trace.  ``kernels``: metric key -> kernel name (the
    ops' name less its ``.N`` suffix).

    Returns ``window_s`` (the harness's ``bench.traced`` span; without it,
    the extent of every event), ``busy_s`` (union of device op
    intervals), ``kernel_s`` (key -> summed device time), ``kernel_calls``,
    ``top_ops`` ([[op name less its suffix, s], ...], 10 largest, clipped
    to the window, control-flow containers left out) and ``idle_gaps``
    ([[label, s], ...]: idle time summed by the host span that covers each
    gap's middle, 10 largest)."""
    dev = tr["device"]
    host = [e for e in tr["host"] if e[2] != WINDOW_SPAN]
    win = [e for e in tr["host"] if e[2] == WINDOW_SPAN]
    if not dev:
        return {"window_s": 0.0, "busy_s": 0.0, "kernel_s": {},
                "kernel_calls": {}, "top_ops": [], "idle_gaps": []}
    lo = min(e[0] for e in dev)
    hi = max(e[0] + e[1] for e in dev)
    if win:
        lo, hi = win[0][0], win[0][0] + win[0][1]
    elif host:
        lo = min(lo, min(e[0] for e in host))
        hi = max(hi, max(e[0] + e[1] for e in host))
    dev = sorted(e for e in dev if lo <= e[0] < hi)
    busy = _merged(dev, lo, hi)
    busy_ns = sum(b - a for a, b in busy)
    kernel_s, calls = {}, {}
    for key, mark in kernels.items():
        hits = [e[1] for e in dev if _op_group(e[2]) == mark]
        kernel_s[key] = sum(hits) * 1e-9
        calls[key] = len(hits)
    groups: dict[str, int] = {}
    for s, d, name, _ in dev:
        g = _op_group(name)
        if g not in CONTAINERS:
            groups[g] = groups.get(g, 0) + min(s + d, hi) - s
    top = sorted(groups.items(), key=lambda kv: -kv[1])[:10]
    # idle gaps between busy intervals, labelled by the innermost host span
    # (the shortest one) that covers the gap's middle
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    labels: dict[str, int] = {}
    spans = np.array([[s, s + d] for s, d, _ in host], np.int64).reshape(-1, 2)
    names = [n for *_, n in host]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        label = "host, no span"
        if len(spans):
            inside = np.nonzero((spans[:, 0] <= mid) & (spans[:, 1] >= mid))[0]
            if inside.size:
                i = inside[np.argmin(spans[inside, 1] - spans[inside, 0])]
                label = names[i]
        labels[label] = labels.get(label, 0) + (b - a)
    gaps = sorted(labels.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "kernel_s": kernel_s, "kernel_calls": calls,
            "top_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}
