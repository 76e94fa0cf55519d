"""Device time by program and the program's step phases: the module runs of
a trace, the phases a ``Hub`` records, and the four readers built on them."""

import gzip
import json
import time
import types
from pathlib import Path

import pytest

import run
from harness import programs, window

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def toy():
    # window 0-100 ms; two decode steps (10-30, 50-70 ms); one admission
    # (72-95 ms: prefill 73-88, guard 88-89, splice 89-91, its sample
    # 92-93); a reset in the bookkeeping after it; a decode step that
    # starts after the window closes
    return {"modules": [
        [10 * MS, 20 * MS, "jit_gear_decode_step", 19 * MS],
        [50 * MS, 20 * MS, "jit_gear_decode_step", 18 * MS],
        [73 * MS, 15 * MS, "jit_gear_prefill_padded", 14 * MS],
        [88 * MS, 1 * MS, "jit_gear_finite_guard", 1 * MS],
        [89 * MS, 2 * MS, "jit_gear_paged_splice", 2 * MS],
        [92 * MS, 1 * MS, "jit_gear_sample", 1 * MS],
        [96 * MS, 1 * MS, "jit_gear_paged_reset", 1 * MS],
        [98 * MS, 20 * MS, "jit_gear_decode_step", 20 * MS]],
        "host": [
        [0, 100 * MS, "bench.traced", {}],
        [9 * MS, 23 * MS, "sched.decode", {}],
        [49 * MS, 23 * MS, "sched.decode", {}],
        [72 * MS, 23 * MS, "sched.admission",
         {"slot": 3, "prompt_tokens": 1500}]]}


def ctx_with(tr):
    # a trace already read: ``programs.of`` returns it without the hub
    return types.SimpleNamespace(programs=tr)


def test_ops_placed_in_their_module_runs():
    runs = [[0, 10, "jit_gear_a"], [20, 10, "jit_gear_b"]]
    ops = [[0, 4, None, None, "fusion.1"], [2, 4, None, None, "copy.3"],
           [22, 3, None, None, "copy.7"], [12, 2, None, None, "copy.8"],
           [14, 3, None, None, "while.2"]]
    mods, groups = programs._place(runs, sorted(ops), 0, 21)
    # a's ops overlap (busy 6); b's op starts after 21, outside the groups
    assert mods == [[0, 10, "jit_gear_a", 6], [12, 5, programs.NO_MODULE, 5],
                    [20, 10, "jit_gear_b", 3]]
    assert sorted(groups) == [["(no module)", "copy", 2],
                              ["jit_gear_a", "copy", 4],
                              ["jit_gear_a", "fusion", 4]]


def test_runs_from_ops_without_a_module_line():
    ops = [[0, 5, "jit_gear_x", 1, "a"], [6, 2, "jit_gear_x", 1, "b"],
           [10, 3, "jit_gear_y", 2, "a"], [20, 1, "jit_gear_x", 3, "a"],
           [30, 1, None, None, "c"]]
    assert programs._runs_from_ops(ops) == [
        [0, 8, "jit_gear_x"], [10, 3, "jit_gear_y"], [20, 1, "jit_gear_x"]]
    assert programs.module_name("jit_gear_decode_step(17)") == "jit_gear_decode_step"


def test_idle_by_phase():
    tr = toy()
    tr["host"].append([30 * MS, 20 * MS, "sched.bookkeeping", {}])
    idle = programs.idle_by_phase(tr)
    # 0-10 and 97-98 ms under no phase, 30-50 in the bookkeeping, 70-73
    # by its middle in the second step, 91-92 and 93-96 in the admission
    assert idle == {"no phase": pytest.approx(0.011),
                    "sched.bookkeeping": pytest.approx(0.020),
                    "sched.decode": pytest.approx(0.003),
                    "sched.admission": pytest.approx(0.004)}


def test_by_module_clips_runs_to_the_window():
    mods = programs.by_module(toy())
    dev, busy, n = mods["jit_gear_decode_step"]
    assert n == 3
    assert dev == pytest.approx(0.042)              # 20 + 20 + 2 ms
    assert busy == pytest.approx(0.039)             # 19 + 18 + 20 x 2/20
    assert programs.window(toy()) == (0, 100 * MS)


def test_device_readers_on_a_toy_trace():
    dec = run.load_reader("decode_device_ms")
    adm = run.load_reader("admission_device_ms_per_ktok")
    assert dec.read(ctx_with(toy())) == pytest.approx(20.0)
    # prefill 15 + guard 1 + splice 2 ms over 1.5 ktok; the sample is not
    # an admission program
    assert adm.read(ctx_with(toy())) == pytest.approx(18.0 / 1.5)


def test_device_readers_on_a_program_without_named_modules():
    """An older program's modules (``jit__lambda``) give nothing to read."""
    tr = toy()
    tr["modules"] = [[s, d, "jit__lambda", b] for s, d, _, b in tr["modules"]]
    tr["host"] = tr["host"][:1]
    for name in ("decode_device_ms", "admission_device_ms_per_ktok"):
        assert run.load_reader(name).read(ctx_with(tr)) is None


def hub_ctx(phases):
    """A traced window of 0-10 s (host clock) whose profiler started at 2 s
    and whose stop stalled the loop from 6 to 8 s."""
    hub = window.Hub([], slots=1, seconds=10.0, trace_dir="unused")
    hub.phases.extend(phases)
    return types.SimpleNamespace(hub=hub, lo=0.0, hi=10.0,
                                 traced=(2.0, 6.0), stall=(6.0, 8.0))


def test_program_span_readers_on_a_synthetic_hub():
    ph = [("sched.admission", 0.5, 1.0, {}), ("sched.admission", 3.0, 4.0, {}),
          ("sched.admission", 5.8, 6.5, {}),        # overlaps the stop stall
          ("sched.bookkeeping", 1.0, 1.002, {}),
          ("sched.bookkeeping", 1.9, 2.1, {}),      # holds the profiler start
          ("sched.bookkeeping", 4.0, 4.004, {}),
          ("sched.bookkeeping", 11.0, 11.5, {})]    # after the window
    ph += [("sched.decode", t, t + 0.1, {}) for t in (1.2, 2.5, 4.1, 9.0)]
    ctx = hub_ctx(ph)
    share = run.load_reader("admission_share").read(ctx)
    # 1.5 s of admissions over the 10 s window less the 2 s stall
    assert share == pytest.approx(100.0 * 1.5 / 8.0)
    gap = run.load_reader("step_host_gap_ms").read(ctx)
    assert gap == pytest.approx(1e3 * 0.006 / 4)


def test_program_span_readers_without_recorded_phases():
    """A program without the recorder (no ``phases``) reads None, as does a
    run whose phases are off."""
    bare = types.SimpleNamespace(hub=types.SimpleNamespace(steps=[]), lo=0.0,
                                 hi=1.0, traced=(0.0, 1.0), stall=(1.0, 1.0))
    off = types.SimpleNamespace(hub=window.Hub([], slots=1, seconds=1.0),
                                lo=0.0, hi=1.0, traced=(0.0, 1.0),
                                stall=(1.0, 1.0))
    for name in ("admission_share", "step_host_gap_ms"):
        for ctx in (bare, off):
            assert run.load_reader(name).read(ctx) is None


def test_hub_records_the_programs_phases():
    hub = window.Hub([], slots=1, seconds=1.0, trace_dir="unused")
    with hub.phase("sched.decode"):
        time.sleep(0.001)
    ((name, t0, t1, args),) = list(hub.phases)
    assert name == "sched.decode" and t1 - t0 >= 0.001 and args == {}


def recorded():
    with gzip.open(DATA / "starcoder2_programs_4s.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_names_every_program():
    """A traced window of ``starcoder2-3b.decode_2k`` recorded on one TPU
    v5e (``record_trace.py``): the served path's programs are ``jit_gear_*``
    and hold nearly all of the device's busy time."""
    tr = recorded()
    assert tr["device"]["platform"] == "tpu"
    mods = programs.by_module(tr)
    busy = sum(b for _, b, _ in mods.values())
    named = sum(b for m, (_, b, _) in mods.items() if m.startswith("jit_gear_"))
    assert named / busy >= 0.95
    steps = programs.annotations(tr, "sched.decode")
    runs = programs.runs(tr, "jit_gear_decode_step")
    assert steps and abs(len(runs) - len(steps)) <= 1
    assert 0 < run.load_reader("decode_device_ms").read(ctx_with(tr)) < 1e3
    assert run.load_reader("admission_device_ms_per_ktok").read(
        ctx_with(tr)) > 0


def test_recorded_annotations_match_the_recorded_phases():
    """Each phase the program recorded on its own clock is one annotation on
    the profiler's timeline, in the same order, as long to 50 us."""
    tr = recorded()
    lo, hi = programs.window(tr)
    ann = [h for h in tr["host"]
           if h[2] != programs.WINDOW_SPAN and lo <= h[0] and h[0] + h[1] <= hi]
    ph = sorted(tr["phases"])
    assert len(ann) == len(ph) > 0
    assert [a[2] for a in ann] == [p[2] for p in ph]
    assert max(abs(a[1] * 1e-9 - (p[1] - p[0])) for a, p in zip(ann, ph)) < 50e-6
