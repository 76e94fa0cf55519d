"""Serving observability: metrics registry, request tracing, fidelity probes.

Enable with ``EngineConfig(obs=ObsConfig(...))`` (or ``obs=True`` for
defaults).  The engine owns one :class:`Observability` per instance; the
scheduler discovers it via ``engine.obs`` and drives the request
lifecycle, the engine feeds prefill annotations and fidelity probes, the
fault injector reports firings.  Everything here is no-op-safe: a
missing/disabled subsystem never raises into the serving path.

See ``docs/observability.md`` for the metric catalog, span schema, and
export formats.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import time

from .catalog import METRICS, MetricSpec, build_registry
from .registry import (CardinalityError, Counter, Gauge, Histogram, Registry,
                       parse_prometheus)
from .tracing import NULL_PHASE, Phase, RequestTrace, Span, Tracer, no_phase

__all__ = [
    "ObsConfig", "Observability",
    "Registry", "Counter", "Gauge", "Histogram", "CardinalityError",
    "parse_prometheus", "Tracer", "Span", "RequestTrace", "Phase",
    "NULL_PHASE", "no_phase", "MetricSpec", "METRICS", "build_registry",
]

# step phases kept per Observability (the oldest drop first): some minutes
# of a serving loop at tens of phases a second
PHASE_LIMIT = 1 << 16


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Telemetry knobs (the ``EngineConfig.obs`` field; ``obs=True``
    coerces to defaults).

    ``metrics``/``tracing`` toggle the registry sync and per-request
    spans.  ``fidelity_every_n`` samples a compression-fidelity probe
    each time the running closed-chunk count crosses a multiple of N
    (0 = off); ``fidelity_budget_frac`` caps measured probe wall time at
    that fraction of elapsed real time.  ``profiler`` records step phases
    (:meth:`Observability.phase`: admissions, decode steps, token reads,
    the host work between steps, and the engine's prefill / guard /
    splice / decode dispatches) on ``time.perf_counter`` and annotates
    each on the ``jax.profiler`` timeline under the same name.
    """

    metrics: bool = True
    tracing: bool = True
    fidelity_every_n: int = 0
    fidelity_budget_frac: float = 0.05
    profiler: bool = False

    def __post_init__(self):
        if self.fidelity_every_n < 0:
            raise ValueError("fidelity_every_n must be >= 0 (0 disables)")
        if not 0.0 < self.fidelity_budget_frac <= 1.0:
            raise ValueError("fidelity_budget_frac must be in (0, 1]")


class Observability:
    """Per-engine telemetry hub: registry + tracer + (optional) fidelity
    probe, with convenience emitters the serving layers call.  All
    emitters are cheap and exception-free by construction (label sets are
    closed; see :mod:`repro.obs.catalog`)."""

    def __init__(self, cfg: ObsConfig, clock=None):
        self.cfg = cfg
        self.clock = time.monotonic if clock is None else clock
        self.registry = build_registry(clock=self.clock)
        self.tracer = Tracer(clock=self.clock, enabled=cfg.tracing)
        self.fidelity = None  # attached by the engine when probes are on
        self._m = bool(cfg.metrics)
        self._synced: dict = {}
        # step phases, (name, t0, t1, args) on perf_counter; None when off
        self.phases: collections.deque | None = None
        if cfg.profiler:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
            self.phases = collections.deque(maxlen=PHASE_LIMIT)

    # -- step phases ---------------------------------------------------------
    def phase(self, name: str, **args):
        """Context manager timing one step phase (see ``ObsConfig.profiler``);
        the shared null context when phases are off."""
        if self.phases is None:
            return NULL_PHASE
        return Phase(self.phases, self._annotation, name, args)

    # -- scheduler lifecycle ----------------------------------------------
    def on_submit(self, rid: int) -> None:
        if self._m:
            self.registry.get("serving_requests_submitted_total").inc()
        self.tracer.start(rid)
        self.tracer.begin(rid, "queued")

    def on_shed(self, rid: int) -> None:
        if self._m:
            self.registry.get("serving_requests_shed_total").inc()
        self.tracer.start(rid)
        self.tracer.finish(rid, "rejected")

    def result(self, status) -> None:
        if self._m:
            self.registry.get("serving_results_total").inc(status=str(status))

    def retry(self, kind: str) -> None:
        if self._m:
            self.registry.get("serving_retries_total").inc(kind=kind)

    def quarantine(self) -> None:
        if self._m:
            self.registry.get("serving_quarantine_total").inc()

    def fault_fired(self, site: str, visit: int) -> None:
        if self._m:
            self.registry.get("serving_faults_injected_total").inc(site=site)
        self.tracer.event_bound("fault", site=site, visit=visit)

    def decode_step(self, seconds: float, n_active: int) -> None:
        if self._m:
            self.registry.get("serving_decode_steps_total").inc()
            self.registry.get("serving_tokens_generated_total").inc(n_active)
            self.registry.get("serving_decode_step_seconds").observe(seconds)

    def decode_grid(self, lengths, grid: tuple[int, int, int]) -> None:
        """One decode step's walk of the GEAR decode kernel, per layer call:
        ``lengths`` [B] are the slots' cache lengths the step attends over,
        ``grid`` is ``Engine.decode_kernel_grid``.  Each of a slot's rows
        (one a KV head, or one for the paged kernel) walks every capacity
        chunk; a step does work only on the row's ``ceil(n_comp / chunk)``
        live chunks (``n_comp``, the compressed extent, is ``length``
        rounded down to a chunk)."""
        if self._m:
            chunk, n_chunks, rows = grid
            live = sum(min(int(n) // chunk, n_chunks) for n in lengths)
            self.registry.get("serving_decode_grid_steps_total").inc(
                len(lengths) * n_chunks * rows)
            self.registry.get("serving_decode_live_steps_total").inc(
                live * rows)

    def queue_depth(self, n: int) -> None:
        if self._m:
            self.registry.get("serving_queue_depth").set(n)

    def observe_prefill(self, seconds: float) -> None:
        if self._m:
            self.registry.get("serving_prefill_seconds").observe(seconds)

    def observe_ttft(self, seconds: float) -> None:
        if self._m:
            self.registry.get("serving_ttft_seconds").observe(seconds)

    def observe_itl(self, seconds: float) -> None:
        if self._m:
            self.registry.get("serving_itl_seconds").observe(seconds)

    def observe_queue_wait(self, seconds: float) -> None:
        if self._m:
            self.registry.get("serving_queue_wait_seconds").observe(seconds)

    def observe_bucket(self, tokens: int) -> None:
        if self._m:
            self.registry.get("serving_prefill_bucket_tokens").observe(tokens)

    # -- lifetime-counter sync --------------------------------------------
    def sync_counter(self, name: str, cumulative: float, **labels) -> None:
        """Mirror an externally-owned cumulative counter (pool/trie stats
        dicts, which reset when their owner is rebuilt) into a registry
        counter by delta; a value below the last-seen one means the
        source was reset, so the whole new value is fresh growth."""
        key = (name, tuple(sorted(labels.items())))
        seen = self._synced.get(key, 0.0)
        if cumulative < seen:
            seen = 0.0
        delta = cumulative - seen
        if delta > 0:
            self.registry.get(name).inc(delta, **labels)
        self._synced[key] = cumulative

    def sync_pool(self, snap) -> None:
        """snap: a PoolSnapshot (serving/pagedpool.py)."""
        if not self._m:
            return
        for field, metric in (("admits", "pool_admits_total"),
                              ("rejects", "pool_rejects_total"),
                              ("shared_pages", "pool_shared_pages_total"),
                              ("fresh_pages", "pool_fresh_pages_total"),
                              ("freed_pages", "pool_freed_pages_total")):
            self.sync_counter(metric, snap[field])
        self.registry.get("pool_free_pages").set(snap["free_pages"])
        self.registry.get("pool_used_pages").set(snap["used_pages"])

    def sync_prefix(self, snap) -> None:
        """snap: a PrefixSnapshot (repro/prefixcache)."""
        if not self._m:
            return
        for field, metric in (
                ("lookup_chunks", "prefix_lookup_chunks_total"),
                ("hit_chunks", "prefix_hit_chunks_total"),
                ("inserts", "prefix_inserts_total"),
                ("evictions", "prefix_evictions_total"),
                ("expiries", "prefix_expiries_total"),
                ("version_evictions", "prefix_version_evictions_total"),
                ("prefill_toks_saved", "prefix_toks_saved_total"),
                ("validate_failures", "prefix_validate_failures_total")):
            self.sync_counter(metric, snap[field])
        self.registry.get("prefix_nodes").set(snap["nodes"])
        self.registry.get("prefix_bytes").set(snap["bytes"])

    # -- exports -----------------------------------------------------------
    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def to_prometheus(self) -> str:
        return self.registry.to_prometheus()

    def to_json(self, indent: int | None = None) -> str:
        return self.registry.to_json(indent=indent)

    def write_metrics_json(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(indent=2))

    def write_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.tracer.to_chrome(), f, indent=2, sort_keys=True)
