"""The window's clock: a traced run's profiler stop does not eat the window."""

import time

import jax

from harness import window

STALL_S = 0.2


def traced_hub(monkeypatch, seconds=5.0):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: time.sleep(STALL_S))
    hub = window.Hub([], slots=1, seconds=seconds, trace_dir="unused")
    hub.t_open = time.perf_counter() - window.TRACE_AFTER_S
    hub.t_close = hub.t_open + seconds
    hub.tick()                                   # starts the profiler
    assert len(hub.trace_t) == 1
    return hub


def test_stop_stall_moves_the_close(monkeypatch):
    hub = traced_hub(monkeypatch)
    close = hub.t_close
    hub.stop_trace()
    t_stop, t_done = hub.trace_t[1:]
    assert t_done - t_stop >= STALL_S
    assert hub.t_close == close + (t_done - t_stop)


def test_stop_after_the_close_moves_nothing(monkeypatch):
    hub = traced_hub(monkeypatch)
    hub.closed = True
    close = hub.t_close
    hub.stop_trace()
    assert hub.t_close == close
