"""The measured window: each client (one per slot) sends its next request
when its last one ended, and the window records, on the host clock, when
each token of each request reached the host.

``Scheduler.run_continuous`` drains a queue and takes no arrivals, so the
harness rides on the telemetry hook the loop already calls: the engine's
``obs`` (a :class:`repro.obs.Observability`) is replaced by a
:class:`Hub`, whose ``decode_step`` / ``observe_prefill`` run inside the
loop after each step's tokens reached the host, and whose tracer sees each
request's admission, each token and each end.  A client's next request is
submitted from there.

The window opens at the first decode step, once every slot holds a request,
and closes ``seconds`` later.  In a traced run the close moves later by the
time ``jax.profiler.stop_trace`` holds the loop (some tens of seconds on
the chip), so that the window still serves for ``seconds``; the readers
leave that stall out (``harness/layers.py``).  Then the scheduler's clock (its deadline
clock) jumps far ahead: every request still queued or in flight times out
at the next step, which drains the loop at once.  Those requests count
neither as done nor as failed, and no token that reached the host after the
close counts.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax

from repro.obs import ObsConfig, Observability
from repro.obs.tracing import Tracer
from repro.serving import Request

CLOSED_JUMP_S = 1e9          # scheduler clock jump at the close
DEADLINE_S = 1e7             # every request's deadline; only the jump hits it
TRACE_AFTER_S = 1.0          # traced runs: the profiler starts this long
TRACE_S = 4.0                # after the window opens, and runs this long


@dataclasses.dataclass
class Rec:
    rid: int
    client: int
    plen: int
    output: int
    due: float                      # perf_counter time it was submitted
    admit: float | None = None      # admission (prefill) started
    tok: list = dataclasses.field(default_factory=list)   # token arrivals
    end: float | None = None
    status: str = ""


class _Recorder(Tracer):
    """Tracer that records the scheduler's per-request calls for the hub."""

    def __init__(self, hub: "Hub"):
        super().__init__(enabled=False)
        self.hub = hub

    def begin(self, rid: int, name: str, **args) -> None:
        if name == "prefill":
            self.hub.on_admit(rid)

    def step(self, rid: int, n: int = 1) -> None:
        self.hub.on_token(rid)

    def finish(self, rid: int, status: str) -> None:
        self.hub.on_finish(rid, str(status))


class Hub(Observability):
    """Clients and clock of one window; see the module docstring."""

    def __init__(self, reqs: list, slots: int, seconds: float,
                 trace_dir: str | None = None):
        super().__init__(ObsConfig(metrics=False, tracing=False,
                                   profiler=trace_dir is not None))
        self.tracer = _Recorder(self)
        self.slots = slots
        self.seconds = float(seconds)
        self.by_client = collections.defaultdict(collections.deque)
        for r in reqs:
            self.by_client[r.client].append(r)
        self.recs: dict[int, Rec] = {}
        self.steps: list[tuple[float, float, list[int]]] = []   # end, s, lengths
        self.admissions: list[tuple[float, float, int, int]] = []  # t0, t1, rid, plen
        self.inflight: dict[int, Rec] = {}        # admitted, not ended
        self.sched = None
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.closed = False
        self._admitting: Rec | None = None
        self._ann = None
        self._step_t = 0.0                        # last decode step's end
        # profiler
        self.trace_dir = trace_dir
        self.trace_t: list[float] = []            # start, stop-call, stop-done
        self._traced_ann = None

    # -- clocks ------------------------------------------------------------
    def sched_clock(self) -> float:
        return time.monotonic() + (CLOSED_JUMP_S if self.closed else 0.0)

    # -- submission -----------------------------------------------------------
    def submit(self, r) -> None:
        self.recs[r.rid] = Rec(rid=r.rid, client=r.client, plen=len(r.prompt),
                               output=r.output, due=time.perf_counter())
        self.sched.submit(Request(rid=r.rid, tokens=r.prompt,
                                  max_new_tokens=r.output,
                                  deadline_s=DEADLINE_S))

    def start_clients(self) -> None:
        """Each client's first request."""
        for c in range(self.slots):
            if self.by_client[c]:
                self.submit(self.by_client[c].popleft())

    def tick(self) -> None:
        """Close the window when it is due; start or stop the profiler."""
        now = time.perf_counter()
        if self.t_close is not None and not self.closed and now >= self.t_close:
            self.closed = True
        self._trace_tick(now)

    # -- callbacks from the scheduler loop ----------------------------------
    def on_admit(self, rid: int) -> None:
        self._end_admission()
        rec = self.recs.get(rid)
        if rec is None:
            return
        rec.admit = time.perf_counter()
        self._admitting = rec
        if self.trace_dir is not None:
            self._ann = jax.profiler.TraceAnnotation("bench.admission")
            self._ann.__enter__()

    def _end_admission(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def observe_prefill(self, seconds: float) -> None:
        now = time.perf_counter()
        self._end_admission()
        rec, self._admitting = self._admitting, None
        if rec is not None:
            rec.tok.append(now)
            self.inflight[rec.rid] = rec
            self.admissions.append((rec.admit, now, rec.rid, rec.plen))
        self.tick()

    def decode_step(self, seconds: float, n_active: int) -> None:
        now = time.perf_counter()
        self._end_admission()
        if self.t_open is None:       # every slot holds a request
            self.t_open, self.t_close = now, now + self.seconds
        # cache lengths after this step's append: prompt + tokens before it
        self.steps.append((now, float(seconds),
                           [r.plen + len(r.tok) for r in self.inflight.values()]))
        self._step_t = now
        self.tick()

    def on_token(self, rid: int) -> None:
        rec = self.recs.get(rid)
        if rec is not None:
            rec.tok.append(self._step_t)

    def on_finish(self, rid: int, status: str) -> None:
        now = time.perf_counter()
        rec = self.recs.get(rid)
        if rec is None:
            return
        rec.end, rec.status = now, status
        self.inflight.pop(rid, None)
        if self._admitting is rec:          # failed in admission
            self._end_admission()
            self._admitting = None
        self.tick()
        if not self.closed and self.by_client[rec.client]:
            self.submit(self.by_client[rec.client].popleft())

    # -- profiler ------------------------------------------------------------
    def _trace_tick(self, now: float) -> None:
        if self.trace_dir is None or self.t_open is None:
            return
        if not self.trace_t and now >= self.t_open + TRACE_AFTER_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._traced_ann = jax.profiler.TraceAnnotation("bench.traced")
            self._traced_ann.__enter__()
            self.trace_t = [time.perf_counter()]
        elif len(self.trace_t) == 1 and (now >= self.trace_t[0] + TRACE_S
                                         or self.closed):
            self.stop_trace()

    def stop_trace(self) -> None:
        if len(self.trace_t) != 1:
            return
        self._traced_ann.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        done = time.perf_counter()
        self.trace_t += [t, done]
        if self.t_close is not None and not self.closed:
            self.t_close += done - t
