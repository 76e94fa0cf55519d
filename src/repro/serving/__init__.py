"""Public serving API.

The blessed surface for building a GEAR-compressed serving stack — entry
points (``repro.launch.serve``), benchmarks, and downstream users import
from here rather than from the submodules:

* :class:`Engine` / :class:`EngineConfig` with the typed knobs
  :class:`AttendPath`, :class:`PrefillMode`, :class:`CacheLayout`
  (plain strings still coerce, as a deprecation shim);
* :class:`Scheduler` with :class:`Request` / :class:`Result` — slot-level
  continuous batching (:meth:`Scheduler.run_continuous`), the one way the
  engine serves requests;
* :class:`CacheView` (:class:`DenseCacheView` / :class:`PagedCacheView`)
  — the slot-protocol facade the scheduler drives;
* the paged pool primitives (:class:`PagePool`, :class:`PagePoolStore`,
  :class:`PoolExhausted`, :func:`pages_needed`) for tooling that inspects
  admission state;
* the resilience layer (docs/serving.md §4): :class:`RequestStatus` /
  :class:`RetryPolicy` / :class:`AdmissionValve` lifecycle primitives,
  :class:`NumericFault` quarantine, and the chaos-test harness
  (:class:`FaultInjector`, :class:`FaultEvent`, :class:`FakeClock`,
  :class:`InjectedFault`);
* the observability layer (docs/observability.md): :class:`ObsConfig` /
  :class:`Observability` (``EngineConfig(obs=...)``), plus the typed
  :class:`PoolSnapshot` / :class:`PrefixSnapshot` stats views that
  ``Scheduler.last_stats`` carries.
"""

from repro.core.cache import NumericFault
from repro.obs import Observability, ObsConfig
from repro.prefixcache import PrefixSnapshot
from repro.serving.engine import (AttendPath, CacheLayout, Engine,
                                  EngineConfig, PrefillMode,
                                  prefix_cache_unsupported_reason)
from repro.serving.faults import (FakeClock, FaultEvent, FaultInjector,
                                  InjectedFault)
from repro.serving.pagedpool import (PagePool, PagePoolStore, PoolExhausted,
                                     PoolSnapshot, pages_needed)
from repro.serving.resilience import AdmissionValve, RequestStatus, RetryPolicy
from repro.serving.sampling import sample
from repro.serving.scheduler import Request, Result, Scheduler
from repro.serving.views import CacheView, DenseCacheView, PagedCacheView

__all__ = [
    "AttendPath", "PrefillMode", "CacheLayout",
    "Engine", "EngineConfig", "prefix_cache_unsupported_reason",
    "Scheduler", "Request", "Result",
    "CacheView", "DenseCacheView", "PagedCacheView",
    "PagePool", "PagePoolStore", "PoolExhausted", "pages_needed",
    "RequestStatus", "RetryPolicy", "AdmissionValve", "NumericFault",
    "FaultInjector", "FaultEvent", "FakeClock", "InjectedFault",
    "ObsConfig", "Observability", "PoolSnapshot", "PrefixSnapshot",
    "sample",
]
