"""Request-level batching scheduler on top of the engine.

Two scheduling modes over the engine's static batch of B *slots*:

* :meth:`Scheduler.run` — wave batching: pending requests are padded into
  fixed-size waves, each wave generates until every member hits EOS or the
  wave's max budget, then the next wave starts.  Simple, but every slot is
  held hostage by the slowest request in its wave.

* :meth:`Scheduler.run_continuous` — slot-level continuous batching: a
  step-loop decodes all B slots each step with per-slot position/done/budget
  vectors; the moment a slot's request hits its own EOS or budget, the next
  queued request is spliced into that slot (batch-1 prefill →
  :meth:`Engine.prefill_slot` batch-row write) while the other slots keep
  decoding undisturbed.  Splice isolation — a spliced request produces
  bit-identical greedy tokens to a solo run — is guaranteed by the per-slot
  cache layout and batch-invariant compression (see DESIGN.md).  Since the
  fused GEAR decode kernel is ragged-aware (per-slot masking inside the
  kernel), mixed-length continuous batches run the same fused
  ``gear_attend`` path as wave mode — ``last_stats["attend_path"]`` reports
  which path the engine compiled.

Both modes trim each request's results at its own first EOS and report
per-request prefill/decode latency.  When the engine has a prefix cache
(``EngineConfig.prefix_cache``), continuous mode threads the scheduler's
admission policy into every slot prefill and reports ``prefix_hit_rate`` /
``prefill_toks_saved`` in ``last_stats``.

**Raw prompts, no scheduler padding.**  Continuous mode hands each
request's RAW token list to :meth:`Engine.prefill_slot`: the engine
length-buckets the prompt up to the next ``n_b`` multiple internally
(bounding jit recompilation to one program per bucket) while cache
lengths, logits, and prefix-trie keys all reflect the true length.  The
trie therefore keys on raw ``n_b``-aligned token chunks, so requests of
*different* lengths sharing a chunk-aligned prefix (the mixed-length
shared-system-prompt workload) hit each other's chunks — see
docs/serving.md and DESIGN.md §4.  Wave mode still left-pads, but only to
the longest raw prompt *within each wave* (a whole wave shares one prefill
program); mixed-length waves therefore shift chunk boundaries per wave —
use continuous mode when prefix reuse or per-request numeric
reproducibility across batch compositions matters.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import NumericFault
from repro.obs import no_phase
from repro.serving.engine import Engine
from repro.serving.faults import InjectedFault
from repro.serving.pagedpool import PoolExhausted, pages_needed
from repro.serving.resilience import AdmissionValve, RequestStatus, RetryPolicy
from repro.serving.sampling import sample

__all__ = ["Request", "Result", "Scheduler"]

_EMPTY = np.zeros(0, np.int32)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray            # [prompt_len] int32
    max_new_tokens: int = 64
    # seconds from submit until the request times out (scheduler clock);
    # None = no deadline.  A queued request past its deadline is dropped
    # with an empty TIMEOUT result; a running one keeps the tokens it
    # generated before the cutoff.
    deadline_s: float | None = None


@dataclasses.dataclass
class Result:
    rid: int
    tokens: np.ndarray            # generated ids, truncated at first EOS
    prefill_s: float
    decode_s: float
    # typed terminal state (resilience layer, docs/serving.md §4); OK and
    # DEGRADED both carry bit-identical tokens — DEGRADED only flags that
    # service was impaired (admission retried / a decode step was retried)
    status: RequestStatus = RequestStatus.OK
    attempts: int = 1             # admission attempts consumed (1 = clean)
    error: str = ""               # human-readable cause for non-OK statuses


class Scheduler:
    """Request queue + batching policy over one :class:`Engine`.

    Construct with the engine and (optionally) the prefix-cache admission
    policy, :meth:`submit` requests, then drain with :meth:`run` (wave
    batching) or :meth:`run_continuous` (slot-level continuous batching —
    the recommended mode; see the module docstring).  Per-run aggregate
    metrics land in :attr:`last_stats`.

    ``prefix_admission`` is threaded to :meth:`Engine.prefill_slot` when
    the engine has a prefix cache: "all" inserts every request's newly
    closed prompt chunks into the trie; "off" reuses cached prefixes but
    admits nothing new (e.g. a bursty one-off workload that would churn
    the eviction budget).

    Resilience knobs (docs/serving.md §4):

    * ``retry`` — :class:`~repro.serving.resilience.RetryPolicy` bounding
      admission retries under pool pressure (and decode-step fault
      retries) with exponential backoff; past the cap the request gets a
      terminal ``REJECTED`` (capacity) / ``FAILED`` (fault) result
      instead of spinning.
    * ``valve`` — :class:`~repro.serving.resilience.AdmissionValve` load
      shedding at :meth:`submit`: beyond ``max_queue`` waiting requests,
      submissions are recorded as immediate ``REJECTED`` results.
    * ``faults`` — a :class:`~repro.serving.faults.FaultInjector`; the
      scheduler wires it into the engine + pool hooks and drives its
      per-iteration environmental faults.  Never set in production.
    * ``clock`` / ``sleep`` — injectable monotonic-seconds source and
      sleeper for deadlines and backoff waits (default: the injector's
      FakeClock when it has one, else ``time.monotonic``/``time.sleep``);
      wall-clock *stats* always use real time.
    """

    def __init__(self, engine: Engine, prefix_admission: str = "all",
                 retry: RetryPolicy | None = None,
                 valve: AdmissionValve | None = None,
                 faults=None, clock=None, sleep=None):
        if prefix_admission not in ("all", "off"):
            raise ValueError(
                f"prefix_admission must be all/off, got {prefix_admission!r}")
        self.engine = engine
        self.prefix_admission = prefix_admission
        self.retry = RetryPolicy() if retry is None else retry
        self.valve = AdmissionValve() if valve is None else valve
        self._faults = faults
        if faults is not None:
            engine.attach_faults(faults)
            if clock is None:
                clock = faults.clock
        self._clock = time.monotonic if clock is None else clock
        self._sleep = (sleep if sleep is not None
                       else getattr(clock, "sleep", time.sleep))
        # telemetry hub (repro.obs), engine-owned; the tracer follows the
        # scheduler's clock so spans line up with deadlines/backoff (and
        # stay deterministic under a FakeClock)
        self.obs = getattr(engine, "obs", None)
        if self.obs is not None:
            self.obs.tracer.clock = self._clock
        self.queue: deque[Request] = deque()
        self.last_stats: dict = {}
        self.submitted_rids: list[int] = []
        self._submit_t: dict[int, float] = {}
        self._shed: list[Result] = []

    def _need_tokens(self, req: Request) -> int:
        """Cache tokens a request's whole lifetime holds: its raw prompt
        (+ VLM prefix) plus one appended token per decode step (the first
        generated token comes from prefill).  True lifetime — paged
        admission reserves exactly these pages, so shorter prompts really
        do cost fewer pages."""
        prefix = (self.engine.cfg.num_prefix_tokens
                  if self.engine.cfg.modality == "vlm" else 0)
        return len(req.tokens) + prefix + req.max_new_tokens - 1

    def submit(self, req: Request) -> None:
        # A request's whole lifetime must fit the engine's cache capacity:
        # past capacity the GEAR streaming buffer would ring-wrap and corrupt
        # the slot silently, so reject at submit time.  A paged engine is
        # additionally bounded by its pool — reject requests that could
        # never be admitted even with every page free (transient pressure,
        # by contrast, just queues; see run_continuous).
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >= 1")
        need = self._need_tokens(req)
        cap = self.engine._cap()
        if need > cap:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.tokens)} + budget "
                f"{req.max_new_tokens} needs {need} cache tokens but engine "
                f"capacity is {cap}")
        pool = self.engine.pool
        if pool is not None:
            pages = pages_needed(need, self.engine.ecfg.policy.buffer_size)
            most = min(pool.n_pages - 1, pool.n_chunks)
            if pages > most:
                raise ValueError(
                    f"request {req.rid}: needs {pages} pool pages but the "
                    f"engine can ever allocate at most {most} to one slot "
                    f"({pool.n_pages - 1} allocatable, {pool.n_chunks} "
                    "block-table entries)")
        self.submitted_rids.append(req.rid)
        self._submit_t[req.rid] = self._clock()
        o = self.obs
        if self.valve.shed(len(self.queue)):
            # load shedding: an immediate terminal result (delivered with
            # the next run) beats queueing behind work that cannot finish
            self._shed.append(Result(
                rid=req.rid, tokens=_EMPTY, prefill_s=0.0, decode_s=0.0,
                status=RequestStatus.REJECTED, attempts=0,
                error=f"shed at submit: queue at max_queue={self.valve.max_queue}"))
            if o is not None:
                o.on_shed(req.rid)
                o.result(str(RequestStatus.REJECTED))
            return
        self.queue.append(req)
        if o is not None:
            o.on_submit(req.rid)
            o.queue_depth(len(self.queue))

    def _drain_shed(self) -> list[Result]:
        out, self._shed = self._shed, []
        return out

    def audit(self, results: list[Result]) -> dict:
        """Post-run invariant report: every submitted rid terminated with
        exactly ONE result, plus the engine's pool/trie audit.  ``results``
        is everything collected from this scheduler's runs.  Never raises.
        """
        counts = Counter(r.rid for r in results)
        issues = [f"rid {rid}: {counts.get(rid, 0)} results (want 1)"
                  for rid in self.submitted_rids if counts.get(rid, 0) != 1]
        issues += [f"rid {rid}: result without a submit"
                   for rid in counts if rid not in set(self.submitted_rids)]
        eng_report = self.engine.audit()
        issues += eng_report["issues"]
        return {"ok": not issues, "issues": issues, "engine": eng_report}

    # ------------------------------------------------------------------
    # Wave mode
    def run(self) -> list[Result]:
        """Drain the queue in engine-batch-sized waves.

        Each wave shares ONE full-batch prefill program, so its prompts are
        left-padded to the wave's longest raw prompt.  Left-padding shifts
        chunk boundaries, so a request's numerics depend on its wave's
        composition — use :meth:`run_continuous` when per-request
        reproducibility or prefix-cache reuse matters.
        """
        results: list[Result] = self._drain_shed()
        B = self.engine.ecfg.batch
        eos = self.engine.ecfg.eos_id
        t_all = time.time()
        while self.queue:
            wave = [self.queue.popleft() for _ in range(min(B, len(self.queue)))]
            while len(wave) < B:                      # pad with a copy slot
                wave.append(Request(rid=-1, tokens=wave[0].tokens,
                                    max_new_tokens=wave[0].max_new_tokens))
            wave_pad = max(len(r.tokens) for r in wave)
            prompts = np.stack([_pad(r.tokens, wave_pad) for r in wave])
            budget = max(r.max_new_tokens for r in wave)
            toks, stats = self.engine.generate(
                {"tokens": jnp.asarray(prompts, jnp.int32)}, budget,
                active=np.array([r.rid >= 0 for r in wave]))
            toks = np.asarray(toks)
            for i, r in enumerate(wave):
                if r.rid < 0:
                    continue
                res = Result(
                    rid=r.rid,
                    tokens=_truncate_eos(toks[i, : r.max_new_tokens], eos),
                    prefill_s=stats["prefill_s"],
                    decode_s=stats["decode_s"])
                results.append(res)
                if self.obs is not None:
                    self.obs.result(str(res.status))
                    self.obs.tracer.finish(r.rid, str(res.status))
        self.last_stats = {"wall_s": time.time() - t_all,
                           "tokens": int(sum(len(r.tokens) for r in results)),
                           "statuses": dict(Counter(str(r.status)
                                                    for r in results))}
        return results

    # ------------------------------------------------------------------
    # Continuous mode
    def run_continuous(self) -> list[Result]:
        """Drain the queue with slot-level continuous batching.

        Greedy-deterministic at ``temperature == 0``: each request's tokens
        are bit-identical to a solo run regardless of what shares the batch.

        Every submitted request terminates with exactly one typed
        :class:`Result` (the chaos suite audits this): admission failures
        (:class:`~repro.serving.pagedpool.PoolExhausted`) retry at most
        ``retry.max_attempts`` times with backoff before a terminal
        ``REJECTED``; a NaN/Inf-poisoned prefill
        (:class:`~repro.core.cache.NumericFault`) fails only that request
        — the engine already rolled back its reservation, so co-batched
        slots continue bit-identically; engine-step faults retry bounded,
        then fail the affected slots; deadlines surface ``TIMEOUT`` with
        whatever tokens existed at the cutoff.
        """
        eng = self.engine
        if eng.cfg.modality == "audio":
            raise NotImplementedError("continuous batching drives text tokens")
        B = eng.ecfg.batch
        eos = eng.ecfg.eos_id
        key = jax.random.PRNGKey(0)

        results: list[Result] = self._drain_shed()
        # the view owns the live cache tree and answers admission for both
        # layouts; dense admission is slot-count-limited (can_admit always
        # True), paged admission is pool-bytes-limited
        view = eng.new_view()
        # engine prefix-cache counters are lifetime-cumulative; snapshot so
        # last_stats reports THIS run's rates, like every other field in it
        # (a paged engine's new_view re-keys the trie, so snapshot AFTER)
        pstats0 = (eng.prefix_cache.snapshot()
                   if eng.prefix_cache is not None else None)
        obs = self.obs
        kernel_grid = eng.decode_kernel_grid if obs is not None else None
        # step phases (ObsConfig.profiler): the loop's host time splits into
        # sched.admission, sched.decode and sched.bookkeeping (the rest)
        phase = obs.phase if obs is not None else no_phase
        recording = obs is not None and obs.phases is not None
        open_phases: dict = {}             # phases that span loop sections
        pos = np.zeros(B, np.int32)        # per-slot absolute decode position
        budget = np.zeros(B, np.int32)     # per-slot remaining-token budget
        done = np.ones(B, bool)            # per-slot idle flag
        fresh = np.ones(B, bool)           # per-slot cache row is empty-state
        reqs: list[Request | None] = [None] * B
        toks_buf: list[list[int]] = [[] for _ in range(B)]
        cur = np.zeros(B, np.int32)        # last sampled token per slot
        prefill_s = np.zeros(B)
        decode_s = np.zeros(B)
        last_tok_t = np.zeros(B)           # when each slot's last token arrived
        steps = 0
        t_decode_total = 0.0
        t_all = time.time()
        attempts: dict[int, int] = {}   # admission/fault retries per rid
        degraded: set[int] = set()      # completed-but-impaired rids
        not_before = 0.0                # admission backoff gate (sched clock)
        dec_faults = 0                  # consecutive failed decode steps

        def phase_open(name: str, **args) -> None:
            if recording and name not in open_phases:
                open_phases[name] = ph = phase(name, **args)
                ph.__enter__()

        def phase_close(name: str) -> None:
            if recording and name in open_phases:
                open_phases.pop(name).__exit__(None, None, None)

        def expired(r: Request) -> bool:
            return (r.deadline_s is not None
                    and self._clock() - self._submit_t.get(r.rid, 0.0)
                    > r.deadline_s)

        def terminal(r: Request, status: RequestStatus, error: str,
                     tokens=_EMPTY) -> None:
            """Emit a non-completion result for a request not in a slot.
            ``attempts`` in the result counts admission attempts consumed
            (already tallied in the dict by the failure handlers)."""
            results.append(Result(
                rid=r.rid, tokens=np.asarray(tokens, np.int32),
                prefill_s=0.0, decode_s=0.0, status=status,
                attempts=attempts.get(r.rid, 0), error=error))
            if obs is not None:
                obs.result(str(status))
                if error:
                    obs.tracer.event(r.rid, "terminal", error=error)
                obs.tracer.finish(r.rid, str(status))

        def reap_expired_queue() -> None:
            """Queued requests past their deadline: empty TIMEOUT results."""
            n = len(self.queue)
            for _ in range(n):
                r = self.queue.popleft()
                if expired(r):
                    terminal(r, RequestStatus.TIMEOUT,
                             f"deadline {r.deadline_s}s elapsed while queued")
                else:
                    self.queue.append(r)

        def finish(s: int, status: RequestStatus | None = None,
                   error: str = "") -> None:
            r = reqs[s]
            if status is None:
                status = (RequestStatus.DEGRADED
                          if attempts.get(r.rid, 0) or r.rid in degraded
                          else RequestStatus.OK)
            results.append(Result(
                rid=r.rid,
                tokens=_truncate_eos(np.asarray(toks_buf[s], np.int32), eos),
                prefill_s=float(prefill_s[s]),
                decode_s=float(decode_s[s]),
                status=status, attempts=attempts.get(r.rid, 0) + 1,
                error=error))
            if obs is not None:
                obs.result(str(status))
                obs.tracer.event(r.rid, "last_token", tokens=len(toks_buf[s]))
                if error:
                    obs.tracer.event(r.rid, "terminal", error=error)
                obs.tracer.finish(r.rid, str(status))
            reqs[s] = None
            done[s] = True
            cur[s] = 0

        def admit_failed(r: Request, exc: Exception,
                         status: RequestStatus) -> bool:
            """Bounded-retry bookkeeping for a failed admission.  Returns
            True when the request was terminally resolved (do not requeue),
            False when it went back to the queue head to retry later."""
            nonlocal not_before
            attempts[r.rid] = attempts.get(r.rid, 0) + 1
            if obs is not None:
                obs.retry("admission")
                obs.tracer.event(r.rid, "retry", kind="admission",
                                 attempt=attempts[r.rid], error=str(exc))
            if attempts[r.rid] >= self.retry.max_attempts:
                terminal(r, status,
                         f"admission failed {attempts[r.rid]}x: {exc}")
                return True
            self.queue.appendleft(r)
            not_before = self._clock() + self.retry.backoff(attempts[r.rid])
            return False

        def splice(s: int) -> bool:
            """Admit the queue head into idle slot ``s``.  True when the
            slot's state may have changed (spliced, or the head resolved
            terminally — the admission loop may try the next request);
            False when the head was requeued for a later retry."""
            phase_close("sched.bookkeeping")
            # queue pop to the first token on the host
            phase_open("sched.admission", slot=s,
                       prompt_tokens=len(self.queue[0].tokens))
            try:
                return admit(s)
            finally:
                phase_close("sched.admission")
                phase_open("sched.bookkeeping")

        def admit(s: int) -> bool:
            r = self.queue.popleft()
            if expired(r):
                terminal(r, RequestStatus.TIMEOUT,
                         f"deadline {r.deadline_s}s elapsed while queued")
                return True
            prompt = np.asarray(r.tokens, np.int32)[None]   # raw, unpadded
            if obs is not None:
                tr = obs.tracer.active.get(r.rid)
                if tr is not None:
                    obs.observe_queue_wait(
                        max(self._clock() - tr.t_submit, 0.0))
                obs.tracer.end(r.rid)   # close "queued"
                obs.tracer.attempt(r.rid)
                obs.tracer.begin(r.rid, "prefill",
                                 attempt=attempts.get(r.rid, 0) + 1, slot=s)
                obs.tracer.bind(r.rid)
            t0 = time.perf_counter()
            try:
                if self._faults is not None:
                    self._faults.check_step("prefill")
                logits = view.prefill_slot(
                    {"tokens": prompt}, s,
                    admit=self.prefix_admission == "all",
                    reserve_tokens=self._need_tokens(r))
            except PoolExhausted as e:
                # can_admit raced another consumer of the pool (e.g. trie
                # admission of a concurrent splice) or the fault injector
                # forced exhaustion: bounded retry, then REJECTED — pages
                # normally come back when a running slot finishes, but an
                # unbounded requeue livelocks under sustained pressure
                return admit_failed(r, e, RequestStatus.REJECTED)
            except NumericFault as e:
                # quarantine: the engine rolled its reservation back and
                # never touched the shared tree; only THIS request fails
                attempts[r.rid] = attempts.get(r.rid, 0) + 1
                terminal(r, RequestStatus.FAILED, f"numeric quarantine: {e}")
                return True
            except InjectedFault as e:
                # transient engine-step fault raised before any device work:
                # bounded retry (it completes DEGRADED), then FAILED
                degraded.add(r.rid)
                return admit_failed(r, e, RequestStatus.FAILED)
            finally:
                if obs is not None:
                    obs.tracer.unbind()
                    obs.tracer.end(r.rid)   # close "prefill"
            first = int(np.asarray(
                eng.sample_next(logits, key, sampler=sample)[0])[0])
            prefill_s[s] = time.perf_counter() - t0
            phase_close("sched.admission")
            if obs is not None:
                now = self._clock()
                last_tok_t[s] = now
                obs.observe_ttft(max(now - self._submit_t.get(r.rid, now), 0.0))
                obs.tracer.event(r.rid, "first_token")
                obs.observe_prefill(float(prefill_s[s]))
            fresh[s] = False
            reqs[s] = r
            toks_buf[s] = [first]
            cur[s] = first
            pos[s] = eng._prompt_len({"tokens": prompt})
            budget[s] = r.max_new_tokens
            decode_s[s] = 0.0
            done[s] = False
            if r.max_new_tokens <= 1 or (eos >= 0 and first == eos):
                finish(s)
            return True

        def head_ready() -> bool:
            """May the queue head attempt admission right now?  Gated on
            the retry backoff window and the view's capacity answer."""
            return (self._clock() >= not_before
                    and view.can_admit(self._need_tokens(self.queue[0])))

        phase_open("sched.bookkeeping")
        while self.queue or not bool(done.all()):
            if self._faults is not None:
                self._faults.tick(eng)
            reap_expired_queue()
            for s in range(B):
                while done[s] and self.queue and head_ready():
                    if not splice(s):
                        break
                if done[s] and not fresh[s]:
                    # queue drained (or head inadmissible): clear the slot so
                    # it idles on an empty cache row instead of decoding
                    # stale request state — and, paged, releases its pages
                    view.reset_slot(s)
                    fresh[s] = True
                    pos[s] = 0
                    cur[s] = 0
            if bool(done.all()):
                if not self.queue:
                    break
                now = self._clock()
                if now < not_before:
                    # idle engine inside a backoff window: sleep it off
                    self._sleep(not_before - now)
                    continue
                # every slot is idle yet the head request was not admitted:
                # the pool's free pages are pinned by the prefix trie.
                # Reclaim (LRU-evict trie entries back into allocatable
                # pages) and retry; when reclaim frees nothing (empty or
                # fully-pinned trie), bounded attempts surface a terminal
                # REJECTED instead of spinning forever.
                r = self.queue[0]
                need = self._need_tokens(r)
                if view.reclaim(need) or view.can_admit(need):
                    continue
                self.queue.popleft()
                if not admit_failed(
                        r, PoolExhausted(
                            f"need {need} tokens, idle engine, reclaim freed "
                            "nothing"),
                        RequestStatus.REJECTED):
                    # requeued for another attempt after its backoff
                    self._sleep(max(not_before - self._clock(), 0.0))
                continue
            if self._faults is not None:
                try:
                    self._faults.check_step("decode")
                except InjectedFault as e:
                    # fault raised BEFORE the jitted step dispatches, so the
                    # donated cache tree is untouched — retry is safe
                    dec_faults += 1
                    active = list(np.nonzero(~done)[0])
                    if obs is not None:
                        obs.retry("decode")
                        for s in active:
                            obs.tracer.event(reqs[s].rid, "retry",
                                             kind="decode",
                                             attempt=dec_faults)
                    if dec_faults >= self.retry.max_attempts:
                        for s in active:
                            finish(s, status=RequestStatus.FAILED,
                                   error=f"decode failed {dec_faults}x: {e}")
                        dec_faults = 0
                    else:
                        degraded.update(reqs[s].rid for s in active)
                        self._sleep(self.retry.backoff(dec_faults))
                    continue
                dec_faults = 0
            phase_close("sched.bookkeeping")
            with phase("sched.decode"):
                t0 = time.perf_counter()
                tb = {"tokens": jnp.asarray(cur[:, None])}
                logits = view.decode(tb, pos)
                with phase("sched.token_read"):
                    tok_dev, key = eng.sample_next(logits, key, steps,
                                                   sampler=sample)
                    nxt = np.asarray(tok_dev)
                step_t = time.perf_counter() - t0
            phase_open("sched.bookkeeping")
            t_decode_total += step_t
            steps += 1
            pos += 1  # idle slots advance harmlessly; a splice rewrites pos[s]
            active_slots = np.nonzero(~done)[0]
            if obs is not None:
                now = self._clock()
                obs.decode_step(step_t, len(active_slots))
                if kernel_grid is not None:
                    # pos now holds each slot's length after the append
                    obs.decode_grid(pos, kernel_grid)
                obs.queue_depth(len(self.queue))
            for s in active_slots:
                decode_s[s] += step_t
                if obs is not None:
                    obs.observe_itl(now - last_tok_t[s])
                    last_tok_t[s] = now
                    obs.tracer.step(reqs[s].rid)
                tok = int(nxt[s])
                toks_buf[s].append(tok)
                cur[s] = tok
                if (eos >= 0 and tok == eos) or len(toks_buf[s]) >= budget[s]:
                    finish(s)
                elif expired(reqs[s]):
                    finish(s, status=RequestStatus.TIMEOUT,
                           error=f"deadline {reqs[s].deadline_s}s elapsed "
                                 "mid-decode")
        phase_close("sched.bookkeeping")

        self.last_stats = {
            "wall_s": time.time() - t_all,
            "decode_s": t_decode_total,
            "decode_steps": steps,
            "tokens": int(sum(len(r.tokens) for r in results)),
            "attend_path": eng.attend_path,
            "layout": str(eng.ecfg.layout),
            "statuses": dict(Counter(str(r.status) for r in results)),
        }
        if eng.pool is not None:
            # typed snapshot; PoolSnapshot indexes like the old dict entry
            self.last_stats["pool"] = eng.pool.snapshot()
        if pstats0 is not None:
            pstats = eng.prefix_cache.snapshot()
            hit = pstats.hit_chunks - pstats0.hit_chunks
            look = pstats.lookup_chunks - pstats0.lookup_chunks
            self.last_stats["prefix_hit_rate"] = hit / max(look, 1)
            self.last_stats["prefill_toks_saved"] = (
                pstats.prefill_toks_saved - pstats0.prefill_toks_saved)
            self.last_stats["prefix_evictions"] = (
                pstats.evictions - pstats0.evictions)
            self.last_stats["prefix_expiries"] = (
                pstats.expiries - pstats0.expiries)
            self.last_stats["prefix_version_evictions"] = (
                pstats.version_evictions - pstats0.version_evictions)
            self.last_stats["prefix"] = pstats
        if obs is not None:
            # fold lifetime component counters into the registry (delta
            # semantics with reset detection — a paged new_view rebuilds
            # the pool/trie and zeroes their cumulative stats)
            if eng.pool is not None:
                obs.sync_pool(self.last_stats["pool"])
            if eng.prefix_cache is not None:
                obs.sync_prefix(eng.prefix_cache.snapshot())
            obs.queue_depth(len(self.queue))
        return results


def _truncate_eos(tokens: np.ndarray, eos_id: int) -> np.ndarray:
    """Trim generated ids at the request's own first EOS (kept inclusive)."""
    if eos_id < 0:
        return tokens
    hits = np.nonzero(tokens == eos_id)[0]
    return tokens[: hits[0] + 1] if hits.size else tokens


def _pad(tokens: np.ndarray, length: int) -> np.ndarray:
    """Left-pad (or left-truncate) to ``length`` — wave mode's per-wave
    prompt alignment; continuous mode sends raw prompts instead."""
    if len(tokens) >= length:
        return tokens[-length:]
    return np.pad(tokens, (length - len(tokens), 0))
