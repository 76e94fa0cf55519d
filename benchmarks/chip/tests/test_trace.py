"""The trace reducer: busy time as a union of device op intervals, kernel
time by name, idle gaps labelled by the host span around them."""

import gzip
import json
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def toy():
    # window 0-10 ms; ops 1-3 and 2-4 (overlap), 6-7 (a kernel); idle
    # 0-1 (no span), 4-6 inside gear.decode, 7-10 inside bench.admission
    return {"device": [[1 * MS, 2 * MS, "fusion.1", "fusion.1 jit(step)"],
                       [2 * MS, 2 * MS, "fusion.2", "fusion.2 jit(step)"],
                       [6 * MS, 1 * MS, "gear_decode_paged.7",
                        "%gear_decode_paged.7 = custom-call(%fusion.1)"],
                       [0, 10 * MS, "while.3", "%while.3 = while(%fusion.2)"]],
            "host": [[0, 10 * MS, "bench.traced"],
                     [3 * MS, 4 * MS, "gear.decode"],
                     [7 * MS, 3 * MS, "bench.admission"]]}


def test_busy_is_union_and_window_is_the_traced_span():
    t = toy()
    t["device"].pop()                                   # the while loop
    r = trace.reduce(t, {"paged": "gear_decode_paged"})
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)          # 1-4 and 6-7 ms
    assert r["kernel_s"] == {"paged": pytest.approx(0.001)}
    assert r["kernel_calls"] == {"paged": 1}


def test_idle_gaps_labelled_by_innermost_span():
    t = toy()
    t["device"].pop()
    r = trace.reduce(t, {})
    gaps = dict(r["idle_gaps"])
    assert gaps == {"bench.admission": pytest.approx(0.003),
                    "gear.decode": pytest.approx(0.002),
                    "host, no span": pytest.approx(0.001)}
    assert r["top_ops"][0] == ["fusion", pytest.approx(0.004)]


def test_kernel_found_by_op_name_not_by_operands():
    t = toy()
    # an op that only reads the kernel's output names it in its text
    t["device"].append([8 * MS, MS, "fusion.9",
                        "%fusion.9 = fusion(%jit_gear_decode_paged_.16)"])
    r = trace.reduce(t, {"paged": "gear_decode_paged"})
    assert r["kernel_calls"] == {"paged": 1}
    # the while loop encloses the other ops: busy all window, not in top ops
    assert r["busy_s"] == pytest.approx(0.010)
    assert "while" not in dict(r["top_ops"])


def test_ops_outside_the_window_do_not_count():
    t = toy()
    t["device"].pop()
    t["device"].append([12 * MS, 5 * MS, "gear_decode_paged.9", ""])
    r = trace.reduce(t, {"paged": "gear_decode_paged"})
    assert r["kernel_calls"] == {"paged": 1}
    assert r["busy_s"] == pytest.approx(0.004)


def test_empty_trace():
    r = trace.reduce({"device": [], "host": []}, {"k": "x"})
    assert r["busy_s"] == 0.0 and r["idle_gaps"] == []


def test_recorded_trace():
    """40 ms of a starcoder2-3b decode step recorded on one TPU v5e (32
    slots x 4096 tokens): the paged decode kernel, once per layer, 10.84 ms
    a call."""
    with gzip.open(DATA / "starcoder2_decode_40ms.json.gz", "rt") as f:
        t = json.load(f)
    r = trace.reduce(t, {"paged": "gear_decode_paged"})
    assert r["window_s"] == pytest.approx(0.040)
    assert r["kernel_calls"] == {"paged": 3}
    assert r["kernel_s"]["paged"] == pytest.approx(0.032527844)
    assert r["busy_s"] == pytest.approx(0.039503149)
    assert r["top_ops"][0][0] == "gear_decode_paged"
    assert r["idle_gaps"] == [["gear.decode", pytest.approx(0.000496851)]]
