"""Paper Fig 3c (throughput) + fused-vs-XLA decode-attend + smokes.

Wall-clock GPU throughput is not reproducible on CPU, so this bench reports:
  (1) the roofline-model predicted decode throughput — decode on a V100 is
      HBM-bandwidth-bound, so tokens/s ≈ batch / ((weights + batch·KV)/BW);
      GEAR's gain comes from the larger feasible batch at equal memory —
      exactly the mechanism behind the paper's 2.1×–5.07×;
  (2) fused vs XLA decode-attend on a mixed-budget continuous-batching
      workload (``--smoke`` / ``--fused`` run only this) — the ragged fused
      ``gear_attend`` path against the portable jnp ``cache.attend`` path.
      On CPU the fused path runs the jnp oracle, so the number is
      layout-relative only; on TPU it is the Pallas kernel and the gap is
      the paper's fused-dequant decode win.
``--chaos`` and ``--obs`` run the resilience and telemetry smokes.

Rows that the CI regression gate (benchmarks/check_regression.py) diffs
against benchmarks/baseline.json carry a machine-readable ``value``.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from benchmarks.common import emit
from benchmarks.bench_memory import kv_bytes_per_seq, max_batch, N_IN, N_GEN
from repro.configs import get_config, smoke_config
from repro.core.policy import FP16, named_policy
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineConfig

V100_BW = 900e9  # bytes/s


def predicted_throughput(policy, cfg, batch):
    weights = cfg.param_count() * 1.0          # 8-bit
    step_bytes = weights + batch * kv_bytes_per_seq(policy, cfg, N_IN + N_GEN)
    return batch / (step_bytes / V100_BW)


def fig3c(cfg):
    pol2 = named_policy("gear_kivi2")
    out = {}
    for name, pol in (("fp16", FP16), ("gear2", pol2)):
        b = max_batch(pol, cfg)
        tps = predicted_throughput(pol, cfg, b)
        out[name] = (b, tps)
        emit(f"fig3c_throughput/{name}", 0.0,
             f"max_batch={b} predicted_tok_per_s={tps:.0f}")
    ratio = out["gear2"][1] / out["fp16"][1]
    emit("fig3c_throughput/ratio", 0.0, f"{ratio:.2f}x paper=2.1-5.07x")
    return ratio


def _mixed_requests(n_reqs: int, max_prompt: int, vocab: int, seed: int = 0):
    """Mixed-length synthetic workload: raw prompt lengths 4..max_prompt
    (no scheduler padding), budgets cycling 8..64."""
    from repro.serving.scheduler import Request
    rng = np.random.RandomState(seed)
    budgets = [8, 16, 32, 64]
    return [Request(rid=i,
                    tokens=rng.randint(1, vocab, size=rng.randint(4, max_prompt + 1)),
                    max_new_tokens=budgets[i % len(budgets)])
            for i in range(n_reqs)]


def fused_vs_xla(key, n_reqs: int = 8, batch: int = 4):
    """Continuous-mode decode throughput: fused gear_attend vs jnp attend.

    Identical mixed-budget workload and scheduler either way; only the
    decode-attend path differs (``EngineConfig.fused``).  The ragged per-slot
    masking inside the kernel is what lets the continuous batches take the
    fused path at all — before it they silently fell back to XLA attend.
    """
    from repro.serving.scheduler import Scheduler
    cfg = smoke_config("llama2-7b")
    m = build_model(cfg)
    params = m.init(key)
    pol = dataclasses.replace(named_policy("gear_kcvt4"),
                              buffer_size=16, rank=2, rank_decode=2)
    max_prompt = 16
    out = {}
    for tag, fused in (("xla", "off"), ("fused", "auto")):
        eng = Engine(m, params, EngineConfig(batch=batch, capacity=96, policy=pol,
                                             eos_id=-1, fused=fused))

        def drive(n: int):
            sched = Scheduler(eng)
            for r in _mixed_requests(n, max_prompt, cfg.vocab_size):
                sched.submit(r)
            sched.run_continuous()
            st = sched.last_stats
            return st["tokens"] / max(st["decode_s"], 1e-9), st["attend_path"]

        drive(2 * batch)                     # compile warmup
        tok_s, path = drive(n_reqs)
        out[tag] = tok_s
        emit(f"throughput_fused/decode_tok_per_s_{tag}", 0.0,
             f"{tok_s:.1f} tok/s attend_path={path}", value=tok_s)
    nbytes = Engine.cache_nbytes(eng.init_caches())
    emit("cache_nbytes/bench_engine_gear", 0.0,
         f"{nbytes} bytes (batch={batch}, cap={eng._cap()})", value=nbytes)
    ratio = out["fused"] / out["xla"]
    emit("throughput_fused/fused_over_xla", 0.0,
         f"{ratio:.2f}x (CPU oracle vs XLA attend; on TPU = Pallas kernel)",
         value=ratio)
    return ratio


def chaos_smoke(key, n_reqs: int = 10, batch: int = 4):
    """Resilience smoke (``--chaos``): the same mixed-length continuous
    workload run fault-free and under a seeded fault schedule.

    Emits:

    * ``chaos/faultfree_ok_rate`` — fraction of requests finishing ``OK``
      on the clean path with the resilience layer armed (retry policy,
      typed statuses, audits).  The regression gate's zero-drop rule pins
      it at 1.0: the resilience machinery must never reject, degrade, or
      fail a healthy request.
    * ``chaos/degraded_decode_tok_per_s`` — decode tok/s under injected
      pool exhaustion / NaN chunks / decode faults (informational:
      degradation should be a slope, not a cliff — the run must still
      terminate with every request accounted for and audits clean).
    * ``chaos/fault_terminal_rate`` — fraction of requests terminally
      REJECTED/FAILED under that schedule (informational).
    """
    from repro.serving import (FakeClock, FaultInjector, RequestStatus,
                               RetryPolicy)
    from repro.serving.scheduler import Scheduler
    cfg = smoke_config("llama2-7b")
    m = build_model(cfg)
    params = m.init(key)
    pol = dataclasses.replace(named_policy("gear_kcvt4"),
                              buffer_size=16, rank=2, rank_decode=2)
    eng = Engine(m, params, EngineConfig(batch=batch, capacity=96, policy=pol,
                                         eos_id=-1, layout="paged"))

    def drive(faults=None):
        eng.attach_faults(None)          # detach the previous run's injector
        sched = Scheduler(eng, faults=faults,
                          retry=RetryPolicy(max_attempts=3, backoff_s=0.01))
        for r in _mixed_requests(n_reqs, 16, cfg.vocab_size):
            sched.submit(r)
        results = sched.run_continuous()
        rep = sched.audit(results)       # zero leaks even under faults
        assert rep["ok"], rep["issues"]
        return results, sched.last_stats

    drive()                              # compile warmup
    clean, cstats = drive()
    ok_rate = sum(r.status is RequestStatus.OK for r in clean) / len(clean)
    emit("chaos/faultfree_ok_rate", 0.0,
         f"{len(clean)} requests, statuses={cstats['statuses']}",
         value=ok_rate)
    inj = FaultInjector(seed=0, clock=FakeClock(),
                        rates={"pool_exhausted": 0.2, "nan_chunk": 0.1,
                               "decode_error": 0.05})
    faulty, fstats = drive(inj)
    tok_s = fstats["tokens"] / max(fstats["decode_s"], 1e-9)
    fired = {k: v for k, v in inj.fired.items() if v}
    emit("chaos/degraded_decode_tok_per_s", 0.0,
         f"{tok_s:.1f} tok/s under seeded faults fired={fired}", value=tok_s)
    n_bad = sum(r.status in (RequestStatus.REJECTED, RequestStatus.FAILED)
                for r in faulty)
    emit("chaos/fault_terminal_rate", 0.0,
         f"{n_bad}/{len(faulty)} REJECTED/FAILED, "
         f"statuses={fstats['statuses']}")
    assert ok_rate == 1.0, \
        f"fault-free path failed requests: {cstats['statuses']}"
    return ok_rate


def obs_smoke(key, n_reqs: int = 8, batch: int = 4):
    """Telemetry overhead + coverage smoke (``--obs``).

    The same mixed-length continuous workload served by two engines that
    differ ONLY in ``EngineConfig.obs`` — off vs full telemetry (metrics +
    tracing + fidelity probes at ``every_n=1``).  Emits
    ``obs/overhead_frac`` = fractional decode tok/s lost with telemetry
    on (median of 3 interleaved drives, clamped at 0); the CI regression
    gate holds it at the committed ceiling.  Also asserts, in-bench, the
    ISSUE 10 acceptance bundle:

    * traces cover 100% of submitted rids, exactly one per rid, with
      statuses matching the scheduler's audit;
    * fidelity probes report per-layer error for >= 1 sampled chunk on
      every GEAR layer;
    * the Prometheus exposition and JSON snapshot both round-trip.
    """
    import json as _json

    from repro.obs import ObsConfig
    from repro.obs.registry import parse_prometheus
    from repro.serving.scheduler import Scheduler
    cfg = smoke_config("llama2-7b")
    m = build_model(cfg)
    params = m.init(key)
    pol = dataclasses.replace(named_policy("gear_kcvt4"),
                              buffer_size=16, rank=2, rank_decode=2)
    # prompts up to 2 chunks long so fidelity probes see closed chunks
    max_prompt = 32
    base = EngineConfig(batch=batch, capacity=96, policy=pol, eos_id=-1)
    eng_off = Engine(m, params, base)
    eng_on = Engine(m, params,
                    dataclasses.replace(base,
                                        obs=ObsConfig(fidelity_every_n=1)))

    def drive(eng):
        if eng.obs is not None:
            eng.obs.tracer.reset()   # one trace per rid per measured drive
        sched = Scheduler(eng)
        reqs = _mixed_requests(n_reqs, max_prompt, cfg.vocab_size)
        for r in reqs:
            sched.submit(r)
        results = sched.run_continuous()
        st = sched.last_stats
        return st["tokens"] / max(st["decode_s"], 1e-9), sched, results, reqs

    drive(eng_off)                   # compile warmup (same jit programs,
    drive(eng_on)                    # but each engine owns its own cache)
    offs, ons = [], []
    for _ in range(3):               # interleaved: drift hits both equally
        offs.append(drive(eng_off)[0])
        tok_on, sched, results, reqs = drive(eng_on)
        ons.append(tok_on)
    off_med = sorted(offs)[1]
    on_med = sorted(ons)[1]
    overhead = max(0.0, 1.0 - on_med / off_med)

    # --- acceptance: trace coverage matches the scheduler's own audit
    o = eng_on.obs
    cov = o.tracer.coverage([r.rid for r in reqs])
    assert cov["complete"], cov
    assert cov["statuses"] == {r.rid: str(r.status) for r in results}, cov
    rep = sched.audit(results)
    assert rep["ok"], rep["issues"]

    # --- acceptance: >= 1 sampled chunk with per-layer error on every
    # GEAR layer (global index r * len(pattern) + i, see FidelityProbe)
    assert o.fidelity is not None and o.fidelity.reports, \
        "no fidelity reports despite every_n=1 and multi-chunk prompts"
    pat = len(cfg.layer_pattern)
    want_layers = {r * pat + i for r in range(cfg.pattern_repeats)
                   for i in o.fidelity._gear_pos}
    layers_seen = {lr["layer"] for rp in o.fidelity.reports
                   for lr in rp["layers"]}
    assert layers_seen == want_layers, (layers_seen, want_layers)
    assert all("k_rel_err" in lr and "v_rel_err" in lr
               for rp in o.fidelity.reports for lr in rp["layers"])

    # --- acceptance: exports round-trip
    parsed = parse_prometheus(o.to_prometheus())
    subm = o.registry.get("serving_requests_submitted_total").value()
    assert parsed[("serving_requests_submitted_total", ())] == subm > 0
    snap = _json.loads(o.to_json())
    assert snap["schema"] == o.snapshot()["schema"]
    assert {mt["name"] for mt in snap["metrics"]} == set(o.registry.names())
    chrome = o.tracer.to_chrome()
    assert len(chrome["traceEvents"]) > 0

    emit("obs/decode_tok_per_s_off", 0.0, f"{off_med:.1f} tok/s telemetry off")
    emit("obs/decode_tok_per_s_on", 0.0,
         f"{on_med:.1f} tok/s metrics+traces+fidelity(every_n=1)")
    emit("obs/overhead_frac", 0.0,
         f"{overhead:.3f} fractional decode tok/s lost (median of 3, "
         f"gate <= 0.05)", value=overhead)
    assert overhead < 0.25, \
        f"telemetry overhead {overhead:.1%} is pathological"
    return overhead


def run(key=None, smoke: bool = False, fused_only: bool = False):
    key = key if key is not None else jax.random.PRNGKey(0)
    fused_ratio = fused_vs_xla(key)
    if smoke or fused_only:
        return fused_ratio
    ratio = fig3c(get_config("llama2-7b"))
    assert 1.5 < ratio < 8.0, ratio
    return ratio


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="the fused-vs-XLA decode-attend comparison only")
    ap.add_argument("--fused", action="store_true",
                    help="only the fused-vs-XLA decode-attend comparison")
    ap.add_argument("--chaos", action="store_true",
                    help="resilience smoke: fault-free ok-rate + degraded "
                         "throughput under a seeded fault schedule")
    ap.add_argument("--obs", action="store_true",
                    help="telemetry smoke: decode tok/s overhead with full "
                         "observability on, plus coverage/fidelity/round-"
                         "trip acceptance asserts")
    ap.add_argument("--json", default=None,
                    help="also write the emitted rows to this JSON file")
    args = ap.parse_args()
    if args.chaos:
        chaos_smoke(jax.random.PRNGKey(0))
    elif args.obs:
        obs_smoke(jax.random.PRNGKey(0))
    else:
        run(smoke=args.smoke, fused_only=args.fused)
    if args.json:
        from benchmarks.common import write_json
        write_json(args.json)
