"""Device time by program, and the program's own step phases, in a traced
window.

Every device program of the served path is a ``jax.jit`` of a function
named ``gear_*`` (``src/repro/serving/engine.py``), so the device plane's
``XLA Modules`` line names each run of it: ``jit_gear_decode_step``,
``jit_gear_prefill_padded``, ...  The program's step phases (``sched.*``,
``gear.*``: ``repro.obs``, on with ``ObsConfig(profiler=True)``, as a traced
run's ``Hub`` has it) are profiler annotations on the host plane, on the
same clock as the device's events, and records ``(name, t0, t1, args)`` on
``time.perf_counter`` in ``hub.phases``.

``load`` keeps a trace in a small JSON-able form (the form the recorded
test trace is kept in):

    {"modules": [[start_ns, dur_ns, module, busy_ns], ...],  # one per run
     "host":    [[start_ns, dur_ns, name, args], ...],        # annotations
     "op_groups": [[module, op group, ns], ...]}              # in the window

``busy_ns`` is the union of the run's ``XLA Ops`` events; ops that lie in
no module run are kept as runs of the module ``(no module)``.  An op group
is an op's name less its ``.N`` suffix (``copy``, ``gear_decode_paged``),
summed over the ops that start in the window, control-flow containers
left out, as ``harness/trace.py`` counts its top ops.  Where a trace has
no module line (the CPU backend), runs are made from the ops' own
``hlo_module`` and ``run_id``.  A program that names no ``gear_*``
module, or records no phases, gives these readers nothing to read: they
return None.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys

from harness import layers, trace

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"     # the harness's span over the traced window
PHASE_PREFIXES = ("sched.", "gear.")
NO_MODULE = "(no module)"


def module_name(event_name: str) -> str:
    """``jit_gear_decode_step(12)`` -> ``jit_gear_decode_step``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def _merged_ns(spans) -> int:
    """Length of the union of ``(start, end)`` spans, sorted by start."""
    total, end = 0, None
    for a, b in spans:
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _runs_from_ops(ops) -> list:
    """Module runs made from ops that carry their module and run id."""
    runs: dict = {}
    for s, d, mod, run_id, _ in ops:
        if mod is None:
            continue
        r = runs.setdefault((mod, run_id), [s, s + d])
        r[0], r[1] = min(r[0], s), max(r[1], s + d)
    return sorted([a, b - a, mod] for (mod, _), (a, b) in runs.items())


def _place(runs: list, ops: list, lo: int = 0, hi: int = 0):
    """``runs`` with each run's busy time (union of the ops inside it), ops
    outside every run as runs of ``NO_MODULE``, and the op groups of the
    ops that start in ``[lo, hi)``, by module."""
    starts = [r[0] for r in runs]
    inside: list[list] = [[] for _ in runs]
    loose = []
    groups: dict = {}
    for s, d, _, _, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][0] + runs[i][1]:
            inside[i].append((s, s + d))
            mod = runs[i][2]
        else:
            loose.append((s, s + d))
            mod = NO_MODULE
        g = trace._op_group(name)
        if lo <= s < hi and g not in trace.CONTAINERS:
            groups[(mod, g)] = groups.get((mod, g), 0) + d
    out = [[a, d, mod, _merged_ns(sorted(spans))]
           for (a, d, mod), spans in zip(runs, inside)]
    for a, b in loose:
        if out and out[-1][2] == NO_MODULE and a <= out[-1][0] + out[-1][1]:
            last = out[-1]
            last[1] = max(last[1], b - last[0])
            last[3] = last[1]
        else:
            out.append([a, b - a, NO_MODULE, b - a])
    return sorted(out), [[m, g, ns] for (m, g), ns in
                         sorted(groups.items(), key=lambda kv: -kv[1])]


def load(trace_dir: str, device: str = "/device:TPU:0") -> dict:
    """The trace under ``trace_dir`` in the module docstring's form."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    runs, ops, host = [], [], []
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    runs += [[int(e.start_ns), int(e.duration_ns),
                              module_name(e.name)] for e in line.events]
                elif line.name == OPS_LINE:
                    for e in line.events:
                        st = dict(e.stats)
                        ops.append([int(e.start_ns), int(e.duration_ns),
                                    st.get("hlo_module"), st.get("run_id"),
                                    trace.op_name(e.name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PHASE_PREFIXES) or e.name == WINDOW_SPAN:
                        args = {k: v for k, v in e.stats
                                if isinstance(v, (int, float, str))}
                        host.append([int(e.start_ns), int(e.duration_ns),
                                     e.name, args])
                    elif device == "cpu":
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            ops.append([int(e.start_ns), int(e.duration_ns),
                                        st.get("hlo_module"), st.get("run_id"),
                                        trace.op_name(e.name)])
    ops.sort(key=lambda o: o[0])
    if not runs:
        runs = _runs_from_ops(ops)
    runs.sort()
    host.sort(key=lambda h: h[0])
    lo, hi = window({"modules": [r + [0] for r in runs], "host": host})
    modules, groups = _place(runs, ops, lo, hi)
    return {"modules": modules, "host": host, "op_groups": groups}


def window(tr: dict) -> tuple[int, int]:
    """The harness's ``bench.traced`` span; without it, every event's extent."""
    for s, d, name, _ in tr["host"]:
        if name == WINDOW_SPAN:
            return s, s + d
    ev = tr["modules"] + tr["host"]
    if not ev:
        return 0, 0
    return min(e[0] for e in ev), max(e[0] + e[1] for e in ev)


def by_module(tr: dict) -> dict[str, list[float]]:
    """module -> [device s, busy s, runs] over the runs in the window (each
    run clipped to it); busy s of a clipped run is scaled with it."""
    lo, hi = window(tr)
    out: dict[str, list[float]] = {}
    for s, d, mod, busy in tr["modules"]:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        frac = (b - a) / d if d else 1.0
        row = out.setdefault(mod, [0.0, 0.0, 0])
        row[0] += (b - a) * 1e-9
        row[1] += busy * frac * 1e-9
        row[2] += 1
    return out


def runs(tr: dict, prefixes, lo: int | None = None, hi: int | None = None):
    """Runs ``[start, dur]`` of the modules whose name starts with one of
    ``prefixes`` and whose start lies in ``[lo, hi)`` (default: the window)."""
    if lo is None:
        lo, hi = window(tr)
    prefixes = (prefixes,) if isinstance(prefixes, str) else tuple(prefixes)
    return [[s, d] for s, d, mod, _ in tr["modules"]
            if lo <= s < hi and mod.startswith(prefixes)]


def annotations(tr: dict, name: str) -> list:
    """``[start, dur, args]`` of the host annotations named ``name`` that
    lie wholly inside the window."""
    lo, hi = window(tr)
    return [[s, d, a] for s, d, n, a in tr["host"]
            if n == name and s >= lo and s + d <= hi]


def of(ctx) -> dict | None:
    """The run's trace in ``load``'s form, read once per run from the trace
    directory (still on disk while the readers run), with each module's
    share of the device's busy time logged; None without a trace."""
    if not hasattr(ctx, "programs"):
        d = getattr(ctx.hub, "trace_dir", None)
        ctx.programs = None
        if d:
            ctx.programs = load(d, "cpu" if ctx.peaks is None
                                else "/device:TPU:0")
            _log(ctx.programs, ctx.trace["busy_s"])
    return ctx.programs


def idle_by_phase(tr: dict) -> dict[str, float]:
    """Device idle seconds between module runs in the window, by the
    innermost ``sched.*``/``gear.*`` annotation over each gap's middle."""
    lo, hi = window(tr)
    ann = [(s, s + d, d, n) for s, d, n, _ in tr["host"] if n != WINDOW_SPAN]
    out: dict[str, float] = {}
    end = lo
    for s, d, *_ in tr["modules"] + [[hi, 0]]:
        if s + d <= lo or s > hi:
            continue
        if s > end:
            mid = (end + min(s, hi)) // 2
            over = [a for a in ann if a[0] <= mid <= a[1]]
            label = min(over, key=lambda a: a[2])[3] if over else "no phase"
            out[label] = out.get(label, 0.0) + (min(s, hi) - end) * 1e-9
        end = max(end, s + d)
    return out


def _log(tr: dict, busy_s: float) -> None:
    rows = sorted(by_module(tr).items(), key=lambda kv: -kv[1][1])
    parts = [f"{m} {b:.6f} s ({100.0 * b / busy_s:.2f}%, {int(n)} runs)"
             if busy_s > 0 else f"{m} {b:.6f} s" for m, (_, b, n) in rows]
    ops = [f"{m}:{g} {ns * 1e-9:.6f} s" for m, g, ns in tr.get("op_groups", [])[:12]]
    idle = sorted(idle_by_phase(tr).items(), key=lambda kv: -kv[1])
    for head, items in (
            ("device busy by module", parts),
            ("top op groups by module", ops),
            ("device idle by phase", [f"{k} {v:.6f} s" for k, v in idle])):
        print(f"programs, {head} in the traced window: " + "; ".join(items),
              file=sys.stderr, flush=True)


def phases(ctx, name: str) -> list | None:
    """``(t0, t1)`` of the program's recorded phases named ``name`` that lie
    in the window, clear of the profiler's start and stop; None when the
    program records no phases."""
    rec = getattr(ctx.hub, "phases", None)
    if rec is None:
        return None
    start = ctx.traced[0]
    return [(t0, t1) for n, t0, t1, _ in list(rec)
            if n == name and layers.clean(ctx, t0, t1)
            and not t0 <= start <= t1]
