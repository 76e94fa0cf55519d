"""Serving telemetry subsystem (ISSUE 10): metrics registry, per-request
tracing, and online compression-fidelity probes.

Three layers under test:

* :mod:`repro.obs.registry` — dependency-free Counter/Gauge/Histogram with
  label sets: cardinality bounds, Prometheus bucket-edge semantics,
  clock-injected snapshot determinism, and text/JSON export round-trips;
* :mod:`repro.obs.tracing` — request-lifecycle spans and events, Chrome
  ``trace_event`` export, and the never-crash contract on unknown rids;
* the serving integration — an obs-enabled :class:`Engine` driven through
  :class:`Scheduler.run_continuous`: 100% trace coverage with statuses
  matching the audit, registry totals matching ``last_stats``, per-layer
  fidelity reports, typed :class:`PoolSnapshot` / :class:`PrefixSnapshot`
  compat, and per-RUN delta semantics of the prefix counters across
  consecutive ``run_continuous`` calls (satellite a).
"""

import dataclasses
import glob
import json
import os

import numpy as np
import pytest

import jax

from repro.configs.base import ModelConfig
from repro.core.policy import named_policy
from repro.models.model import build_model
from repro.obs import NULL_PHASE, Observability, ObsConfig
from repro.obs.catalog import METRICS, build_registry
from repro.obs.registry import (METRICS_SCHEMA, CardinalityError, Registry,
                                parse_prometheus)
from repro.obs.tracing import TRACE_SCHEMA, Tracer
from repro.serving import (Engine, EngineConfig, FakeClock, Request,
                           RequestStatus, Scheduler)

pytestmark = pytest.mark.obs

EOS = 3
TINY = ModelConfig(name="tiny-obs", family="dense", num_layers=2, d_model=32,
                   num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                   vocab_size=64)


def _small():
    pol = named_policy("gear_kcvt4")
    return dataclasses.replace(pol, buffer_size=8, group=8, rank=2,
                               rank_decode=2)


_SHARED: dict = {}


def _model():
    if "model" not in _SHARED:
        m = build_model(TINY)
        _SHARED["model"] = (m, m.init(jax.random.PRNGKey(0)))
    return _SHARED["model"]


def _obs_engine():
    """One shared paged obs-on engine (jit programs are the slow part)."""
    if "engine" not in _SHARED:
        m, params = _model()
        _SHARED["engine"] = Engine(
            m, params, EngineConfig(batch=2, capacity=48, policy=_small(),
                                    eos_id=EOS, layout="paged",
                                    obs=ObsConfig(fidelity_every_n=1)))
    return _SHARED["engine"]


def _requests(n=5, seed=0, min_len=10, max_len=20):
    rng = np.random.RandomState(seed)
    budgets = [6, 3, 9, 1, 5, 7, 2][:n]
    return [Request(rid=i,
                    tokens=rng.randint(4, 64, size=rng.randint(min_len, max_len)),
                    max_new_tokens=b)
            for i, b in enumerate(budgets)]


# ---------------------------------------------------------------------------
# Registry


def test_counter_and_gauge_basics():
    r = Registry()
    c = r.counter("reqs_total", "requests", labels=("status",))
    c.inc(status="ok")
    c.inc(2.0, status="ok")
    c.inc(status="failed")
    assert c.value(status="ok") == 3.0
    assert c.value(status="failed") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1.0, status="ok")
    with pytest.raises(ValueError):        # undeclared label name
        c.inc(shard="0")
    g = r.gauge("depth", "queue depth")
    g.set(4)
    g.dec()
    assert g.value() == 3.0
    # series are deterministically ordered by label values
    assert [s["labels"]["status"] for s in c.series()] == ["failed", "ok"]


def test_label_cardinality_bound():
    r = Registry()
    c = r.counter("c_total", "bounded", labels=("rid",), max_label_sets=3)
    for i in range(3):
        c.inc(rid=str(i))
    with pytest.raises(CardinalityError):
        c.inc(rid="explodes")
    c.inc(rid="1")                         # existing series still fine
    assert c.value(rid="1") == 2.0


def test_histogram_bucket_edges():
    r = Registry()
    h = r.histogram("lat_seconds", "latency", buckets=(1.0, 2.0, 5.0))
    for v in (1.0, 1.0000001, 2.0, 5.0, 7.0):   # le-INclusive edges
        h.observe(v)
    (s,) = h.series()
    by_le = {b["le"]: b["count"] for b in s["buckets"]}
    assert by_le == {1.0: 1, 2.0: 3, 5.0: 4, "+Inf": 5}   # cumulative
    assert s["count"] == 5 and s["sum"] == pytest.approx(16.0000001)
    with pytest.raises(ValueError):        # unsorted buckets
        r.histogram("bad_seconds", "x", buckets=(2.0, 1.0))


def test_registry_reregistration_and_lookup():
    r = Registry()
    c1 = r.counter("x_total", "help", labels=("a",))
    assert r.counter("x_total", "help", labels=("a",)) is c1
    with pytest.raises(ValueError):        # same name, different spec
        r.counter("x_total", "help", labels=("b",))
    with pytest.raises(ValueError):        # kind clash
        r.gauge("x_total", "help", labels=("a",))
    with pytest.raises(KeyError):
        r.get("unregistered")
    assert "x_total" in r and "nope" not in r


def test_snapshot_deterministic_under_injected_clock():
    def build():
        clock = FakeClock(100.0)
        r = Registry(clock=clock)
        c = r.counter("ops_total", "ops", labels=("kind",))
        h = r.histogram("dt_seconds", "dt", buckets=(0.1, 1.0))
        for kind, dt in (("b", 0.05), ("a", 0.5), ("b", 2.0)):
            c.inc(kind=kind)
            h.observe(dt)
            clock.advance(1.0)
        return r
    a, b = build(), build()
    assert a.to_json() == b.to_json()
    assert a.to_prometheus() == b.to_prometheus()
    assert a.snapshot()["time"] == 103.0
    assert a.snapshot()["schema"] == METRICS_SCHEMA


def test_prometheus_round_trip_with_hostile_labels():
    r = Registry()
    c = r.counter("c_total", 'he says "hi"\nand leaves', labels=("path",))
    c.inc(3, path='a"b\\c\nd')             # quote, backslash, newline
    g = r.gauge("g", "plain")
    g.set(-2.5)
    h = r.histogram("h_seconds", "hist", buckets=(0.5, 1.0))
    h.observe(0.25)
    parsed = parse_prometheus(r.to_prometheus())
    assert parsed[("c_total", (("path", 'a"b\\c\nd'),))] == 3.0
    assert parsed[("g", ())] == -2.5
    assert parsed[("h_seconds_bucket", (("le", "0.5"),))] == 1.0
    assert parsed[("h_seconds_bucket", (("le", "+Inf"),))] == 1.0
    assert parsed[("h_seconds_count", ())] == 1.0
    with pytest.raises(ValueError):
        parse_prometheus("not a sample line at all{")


def test_catalog_preregisters_every_metric():
    reg = build_registry()
    names = set(reg.names())
    assert {m.name for m in METRICS} == names
    for m in METRICS:
        assert reg.get(m.name).kind == m.kind
        assert tuple(reg.get(m.name).label_names) == tuple(m.labels)


# ---------------------------------------------------------------------------
# Tracer


def test_tracer_lifecycle_and_chrome_export():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    tr.start(7)
    tr.begin(7, "queued")
    clock.advance(1.0)
    tr.end(7)
    tr.begin(7, "prefill", attempt=1)
    tr.event(7, "fault", site="nan_chunk")
    clock.advance(2.0)
    tr.end(7)
    tr.step(7)
    tr.step(7)
    tr.finish(7, "ok")
    cov = tr.coverage([7])
    assert cov["complete"] and cov["statuses"] == {7: "ok"}
    doc = json.loads(tr.to_json())
    assert doc["schema"] == TRACE_SCHEMA
    names = [(e["name"], e["ph"]) for e in doc["traceEvents"]]
    assert ("request", "X") in names and ("prefill", "X") in names
    assert ("fault", "i") in names
    req = next(e for e in doc["traceEvents"] if e["name"] == "request")
    assert req["args"]["decode_steps"] == 2
    assert req["dur"] == pytest.approx(3e6)          # µs


def test_tracer_unknown_rid_and_duplicate_start():
    tr = Tracer(clock=FakeClock())
    # unknown rids never crash serving
    tr.begin(99, "x")
    tr.end(99)
    tr.event(99, "y")
    tr.step(99)
    tr.finish(99, "ok")
    assert tr.completed == []
    tr.start(1)
    tr.start(1)                            # resubmit: old trace kept as evidence
    tr.finish(1, "ok")
    assert [t.status for t in tr.completed] == ["abandoned", "ok"]
    cov = tr.coverage([1])
    assert not cov["complete"] and cov["duplicates"] == [1]


def test_tracer_bound_annotations_and_disabled():
    tr = Tracer(clock=FakeClock())
    tr.annotate(x=1)                       # unbound: no-op, no crash
    tr.event_bound("nope")
    with tr.span_bound("nothing"):
        pass
    tr.start(1)
    tr.begin(1, "prefill")
    tr.bind(1)
    tr.annotate(bucket_tokens=16)
    with tr.span_bound("splice"):
        pass
    tr.event_bound("quarantine")
    tr.unbind()
    tr.end(1)
    tr.finish(1, "ok")
    (t,) = tr.completed
    assert {s.name for s in t.spans} == {"prefill", "splice"}
    prefill = next(s for s in t.spans if s.name == "prefill")
    assert prefill.args["bucket_tokens"] == 16
    assert [name for name, _, _ in t.events] == ["quarantine"]

    off = Tracer(enabled=False)
    off.start(5)
    off.finish(5, "ok")
    assert off.completed == [] and off.active == {}


# ---------------------------------------------------------------------------
# Config plumbing + typed snapshots


def test_engineconfig_obs_coercion():
    kw = dict(batch=1, capacity=32, policy=_small())
    assert EngineConfig(**kw).obs is None
    assert EngineConfig(**kw, obs=False).obs is None
    assert EngineConfig(**kw, obs=True).obs == ObsConfig()
    got = EngineConfig(**kw, obs={"fidelity_every_n": 4}).obs
    assert got == ObsConfig(fidelity_every_n=4)
    with pytest.raises(ValueError):
        EngineConfig(**kw, obs=42)
    with pytest.raises(ValueError):
        ObsConfig(fidelity_every_n=-1)
    with pytest.raises(ValueError):
        ObsConfig(fidelity_budget_frac=0.0)


def test_sync_counter_delta_and_reset_clamp():
    o = Observability(ObsConfig())
    o.sync_counter("pool_admits_total", 5)
    o.sync_counter("pool_admits_total", 8)
    assert o.registry.get("pool_admits_total").value() == 8.0
    # a rebuilt pool restarts its cumulative stats at 0: the counter must
    # clamp (treat the new stream as fresh), never go backwards or crash
    o.sync_counter("pool_admits_total", 2)
    assert o.registry.get("pool_admits_total").value() == 10.0


def test_prefix_snapshot_dict_compat():
    from repro.prefixcache import PrefixCache
    pc = PrefixCache(chunk=2, budget_bytes=1 << 20)
    snap = pc.snapshot()
    assert snap["lookups"] == snap.lookups == 0
    assert snap.as_dict()["budget_bytes"] == 1 << 20
    with pytest.raises(KeyError):
        snap["not_a_field"]


# ---------------------------------------------------------------------------
# Serving integration (shared obs engine; compile-heavy)


@pytest.mark.slow
def test_end_to_end_coverage_metrics_and_fidelity():
    eng = _obs_engine()
    o = eng.obs
    o.tracer.reset()
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    results = sched.run_continuous()
    rep = sched.audit(results)
    assert rep["ok"], rep["issues"]

    # exactly one finished trace per submitted rid, statuses = audit truth
    cov = o.tracer.coverage([r.rid for r in reqs])
    assert cov["complete"], cov
    assert cov["statuses"] == {r.rid: str(r.status) for r in results}

    # registry totals agree with the scheduler's own accounting
    reg = o.registry
    total = sum(s["value"] for s in reg.get("serving_results_total").series())
    assert total == len(results)
    by_status = {s["labels"]["status"]: s["value"]
                 for s in reg.get("serving_results_total").series()}
    assert by_status == {k: float(v)
                         for k, v in sched.last_stats["statuses"].items()}
    assert reg.get("serving_requests_submitted_total").value() == len(reqs)
    assert reg.get("serving_decode_steps_total").value() > 0

    # fidelity probes: >= 1 sampled chunk reported on every GEAR layer
    assert o.fidelity is not None and o.fidelity.reports
    pat = len(TINY.layer_pattern)
    want = {rep_i * pat + i for rep_i in range(TINY.pattern_repeats)
            for i in o.fidelity._gear_pos}
    seen = {lr["layer"] for rp in o.fidelity.reports for lr in rp["layers"]}
    assert seen == want
    assert all(np.isfinite(lr["k_rel_err"]) and np.isfinite(lr["v_rel_err"])
               for rp in o.fidelity.reports for lr in rp["layers"])

    # typed pool snapshot rides last_stats with dict-style compat
    pool = sched.last_stats["pool"]
    assert pool["admits"] == pool.admits >= len(results)
    with pytest.raises(KeyError):
        pool["bogus"]

    # exports round-trip on the live registry
    parsed = parse_prometheus(o.to_prometheus())
    assert parsed[("serving_requests_submitted_total", ())] == len(reqs)
    snap = json.loads(o.to_json())
    assert {m["name"] for m in snap["metrics"]} == set(reg.names())


@pytest.mark.slow
def test_prefix_counters_are_per_run_deltas():
    """Satellite (a): ``last_stats`` prefix counters reset every
    ``run_continuous`` call while the registry keeps lifetime totals."""
    m, params = _model()
    clock = FakeClock()
    eng = Engine(m, params,
                 EngineConfig(batch=1, capacity=48, policy=_small(),
                              eos_id=-1, prefix_cache=True,
                              prefill_mode="streaming",
                              prefix_cache_ttl=60.0, obs=True),
                 clock=clock)
    shared = np.arange(4, 20, dtype=np.int64) % 60 + 4    # two 8-token chunks
    reqs = [np.concatenate([shared, [5 + i, 6, 7 + i]]) for i in range(3)]

    def run_once():
        sched = Scheduler(eng, clock=clock, sleep=clock.sleep)
        for i, toks in enumerate(reqs):
            sched.submit(Request(rid=run_once.rid + i, tokens=toks,
                                 max_new_tokens=2))
        run_once.rid += 100
        sched.run_continuous()
        return sched.last_stats
    run_once.rid = 0

    st1 = run_once()                      # cold: request 1 seeds the trie
    st2 = run_once()                      # warm: every request hits
    st3 = run_once()
    assert st1["prefill_toks_saved"] < st2["prefill_toks_saved"]
    # per-RUN delta: an identical warm run reports the same saving, not a
    # lifetime-cumulative doubling
    assert st2["prefill_toks_saved"] == st3["prefill_toks_saved"] > 0
    assert st3["prefix"].prefill_toks_saved == (
        st1["prefill_toks_saved"] + 2 * st2["prefill_toks_saved"])
    assert st2["prefix_expiries"] == st3["prefix_expiries"] == 0

    clock.advance(120.0)                  # past the 60s TTL
    st4 = run_once()
    assert st4["prefix_expiries"] >= 1    # this run drained stale chunks
    st5 = run_once()
    assert st5["prefix_expiries"] == 0    # delta, not lifetime
    assert st5["prefix"].expiries >= 1    # lifetime stays in the snapshot
    # the registry counter tracks the lifetime total via sync_counter
    assert (eng.obs.registry.get("prefix_expiries_total").value()
            == st5["prefix"].expiries)


# ---------------------------------------------------------------------------
# Step phases (ObsConfig.profiler) and named programs


def test_phase_recorder_off_by_default(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **a: opened.append(name))
    o = Observability(ObsConfig())
    assert o.phases is None
    ph = o.phase("sched.decode", slot=1)
    assert ph is NULL_PHASE
    with ph:
        pass
    assert opened == []


def _phase_engine():
    if "phase_engine" not in _SHARED:
        m, params = _model()
        _SHARED["phase_engine"] = Engine(
            m, params, EngineConfig(
                batch=2, capacity=48, policy=_small(), eos_id=-1,
                layout="paged", prefill_mode="streaming",
                obs=ObsConfig(metrics=True, tracing=False, profiler=True)))
    return _SHARED["phase_engine"]


def _run_phases(eng, reqs):
    eng.obs.phases.clear()
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(r)
    results = sched.run_continuous()
    return sched, results, list(eng.obs.phases)


def _within(inner, outers) -> bool:
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_phases_of_a_continuous_run():
    eng = _phase_engine()
    reqs = _requests()
    sched, results, ph = _run_phases(eng, reqs)
    by = {}
    for p in ph:
        by.setdefault(p[0], []).append(p)
    steps = sched.last_stats["decode_steps"]
    # one admission per admitted request, one decode and token read a step
    assert len(by["sched.admission"]) == len(reqs)
    assert sorted(a[3]["prompt_tokens"] for a in by["sched.admission"]) == \
        sorted(len(r.tokens) for r in reqs)
    assert len(by["sched.decode"]) == len(by["sched.token_read"]) == steps
    assert len(by["gear.decode"]) == steps
    # nesting: the step's dispatch and token read inside its decode phase,
    # the engine's prefill, guard and splice inside the admission
    for inner, outer in (("sched.token_read", "sched.decode"),
                         ("gear.decode", "sched.decode"),
                         ("gear.prefill", "sched.admission"),
                         ("gear.guard", "sched.admission"),
                         ("gear.splice", "sched.admission")):
        assert by[inner] and all(_within(p, by[outer]) for p in by[inner])
    # the bookkeeping between steps overlaps no admission and no step
    loop = sorted(by["sched.admission"] + by["sched.decode"]
                  + by["sched.bookkeeping"], key=lambda p: p[1])
    assert all(a[2] <= b[1] for a, b in zip(loop, loop[1:]))
    # first and last tokens reach the operators' histograms
    reg = eng.obs.registry
    ttft = reg.get("serving_ttft_seconds").series()[0]
    itl = reg.get("serving_itl_seconds").series()[0]
    assert ttft["count"] >= len(reqs)
    n_tok = sum(len(r.tokens) for r in results)
    assert itl["count"] >= n_tok - len(reqs)


def test_served_programs_are_named_and_annotated(tmp_path):
    """Every device program the continuous loop runs is a ``jit_gear_*``
    module, and each recorded phase is one profiler annotation."""
    eng = _phase_engine()
    _run_phases(eng, _requests())              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, _, ph = _run_phases(eng, _requests(seed=1))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ann, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("sched.", "gear.")):
                    ann.append((e.start_ns, e.duration_ns, e.name))
                else:
                    st = dict(e.stats)
                    if "hlo_module" in st:
                        ops.append((e.start_ns, st["hlo_module"]))
    ann.sort()
    assert [a[2] for a in ann] == [p[0] for p in sorted(ph, key=lambda p: p[1])]
    # from the first admission on (the run's set-up builds its caches and
    # key before it), nothing runs but the engine's named programs
    t0 = min(a[0] for a in ann if a[2] == "sched.admission")
    mods = {m for t, m in ops if t >= t0}
    assert {"jit_gear_decode_step", "jit_gear_prefill_padded",
            "jit_gear_finite_guard", "jit_gear_paged_splice",
            "jit_gear_sample"} <= mods
    assert all(m.startswith("jit_gear_") for m in mods), mods


def test_decode_grid_counts_live_chunks():
    """Ragged slot lengths: grid steps are rows x capacity chunks, live
    steps are KV heads x sum of ceil(n_comp / chunk), with n_comp the
    length rounded down to a chunk; metrics off counts nothing."""
    lengths = np.asarray([0, 7, 8, 17, 40, 63])     # capacity 6 x 8 = 48
    chunk, n_chunks, heads = 8, 6, 2
    o = Observability(ObsConfig())
    o.decode_grid(lengths, (chunk, n_chunks, heads))
    o.decode_grid(lengths[:2], (chunk, n_chunks, heads))
    n_comp = np.minimum(lengths // chunk * chunk, n_chunks * chunk)
    live = heads * int(np.sum(-(-n_comp // chunk)))          # 2 * 14
    reg = o.registry
    assert reg.get("serving_decode_grid_steps_total").value() == 8 * 6 * 2
    assert reg.get("serving_decode_live_steps_total").value() == live == 28
    off = Observability(ObsConfig(metrics=False))
    off.decode_grid(lengths, (chunk, n_chunks, heads))
    assert off.registry.get("serving_decode_grid_steps_total").value() == 0


def test_decode_grid_counters_follow_the_cache(monkeypatch):
    """Over a continuous run, the counters equal what each decode step's
    cache lengths (read back from the device) give."""
    from repro.core.cache import PagedGEARLayerCache
    eng = _obs_engine()
    chunk, n_chunks, heads = eng.decode_kernel_grid
    assert (chunk, heads) == (8, TINY.num_kv_heads) and n_chunks == 6
    reg = eng.obs.registry
    grid0 = reg.get("serving_decode_grid_steps_total").value()
    live0 = reg.get("serving_decode_live_steps_total").value()
    seen = []
    real = eng.decode

    def spy(token_batch, caches, pos):
        logits, caches = real(token_batch, caches, pos)
        layer = next(c for c in jax.tree.leaves(
            caches, is_leaf=lambda c: isinstance(c, PagedGEARLayerCache))
            if isinstance(c, PagedGEARLayerCache))
        seen.append(np.asarray(layer.length).reshape(-1, 2)[0])
        return logits, caches

    monkeypatch.setattr(eng, "decode", spy)
    sched = Scheduler(eng)
    for r in _requests():
        sched.submit(r)
    sched.run_continuous()
    assert len(seen) == sched.last_stats["decode_steps"] > 0
    n_comp = np.minimum(np.stack(seen) // chunk * chunk, n_chunks * chunk)
    want_live = heads * int(np.sum(-(-n_comp // chunk)))
    grid = reg.get("serving_decode_grid_steps_total").value() - grid0
    live = reg.get("serving_decode_live_steps_total").value() - live0
    assert grid == len(seen) * 2 * heads * n_chunks
    assert live == want_live > 0


def test_trace_records_first_and_last_token():
    eng = _obs_engine()
    eng.obs.tracer.reset()
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    results = sched.run_continuous()
    got = {r.rid: len(r.tokens) for r in results}
    for tr in eng.obs.tracer.completed:
        names = [n for n, _, _ in tr.events]
        assert names.count("first_token") == names.count("last_token") == 1
        first = next(t for n, t, _ in tr.events if n == "first_token")
        last, args = next((t, a) for n, t, a in tr.events if n == "last_token")
        assert first <= last and args["tokens"] >= got[tr.rid]
        assert "decode" not in {s.name for s in tr.spans}
