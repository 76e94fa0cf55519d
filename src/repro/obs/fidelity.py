"""Online compression-fidelity probes.

GEAR's headline claim is *near-lossless* compression; the parity tests
prove it offline, these probes measure it **in production**, per layer,
on live traffic.  The engine calls :meth:`FidelityProbe.maybe_probe`
right after each prefill's numeric guard — on the read-only batch-1
cache tree, *before* the donating splice — so probing can never perturb
serving state (the probe-parity sweep in ``tests/test_cache.py`` pins
caches and logits bit-identical probe-on vs probe-off).

Mechanics per sampled request:

1. **Shadow reference.** Streaming prefill discards the raw K/V, so the
   probe reruns the prompt through a jitted fp16 monolithic prefill
   (``ref_prefill``, built by the engine from the same model/params with
   the :data:`~repro.core.policy.FP16` policy at the same capacity).
   FP16 cache leaves at a GEAR position are exactly the uncompressed
   K/V, position-aligned with the compressed tree.
2. **Reconstruction compare.**  One jitted program vmaps
   :func:`repro.core.cache.dense_kv` over the repeat axis of every GEAR
   position and reduces masked-Frobenius statistics over the *closed*
   region (``tok < (length // n_b) * n_b`` — the buffer tail is stored
   fp16 and trivially exact).  Masking with the traced length means one
   program total, not one per prompt length.  Per layer it records
   relative Frobenius error of K̂/V̂ (:func:`repro.core.metrics.rel_frobenius`
   semantics), low-rank residual share, and sparse-outlier mass; plus
   the max-abs last-position logits drift vs the shadow.
3. **Budget throttle.** Probes cost a full fp16 prefill, so a measured
   wall-clock budget (``budget_frac`` of elapsed real time since the
   probe was created) skips sampling when probing would exceed it —
   counted in ``fidelity_probe_skipped_total``, never blocking serving.
   The throttle uses ``time.perf_counter`` (not the injectable serving
   clock) because it compares *real* costs; the first eligible probe
   always runs.

Sampling is "every Nth closed chunk": a running count of closed chunks
crossing a multiple of ``every_n`` triggers a probe, so heavier prompts
are sampled proportionally more.  Failures inside a probe increment
``fidelity_probe_errors_total`` and are swallowed — telemetry must never
take down serving.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core.metrics import masked_rel_frobenius, masked_share

__all__ = ["FidelityProbe"]


class FidelityProbe:
    """See module docstring.

    Parameters
    ----------
    ref_prefill: callable(batch1_dict) -> (logits, caches)
        Jitted fp16 monolithic prefill of the engine's model/params.
    cache_cfgs: per-pattern-position batch-1 ``CacheConfig`` (``None``
        for positions without one, e.g. rwkv) — only ``kind == "gear"``
        positions are probed.
    registry: the obs :class:`~repro.obs.registry.Registry`.
    every_n: sample a probe each time the running closed-chunk count
        crosses a multiple of this (0 disables).
    budget_frac: measured-overhead ceiling as a fraction of real
        elapsed time.
    """

    def __init__(self, ref_prefill, cache_cfgs, policy, registry,
                 every_n: int, budget_frac: float = 0.05,
                 max_reports: int = 256):
        self._ref_prefill = ref_prefill
        self._ccfgs = list(cache_cfgs)
        self._pol = policy
        self._reg = registry
        self.every_n = int(every_n)
        self.budget_frac = float(budget_frac)
        self._gear_pos = [i for i, c in enumerate(self._ccfgs)
                          if c is not None and c.kind == "gear"]
        self._n_unit = len(self._ccfgs)
        self._chunks_seen = 0
        self._spent_s = 0.0
        self._born = time.perf_counter()
        self._fn = None  # jitted compare, built lazily on first probe
        self.reports: collections.deque = collections.deque(maxlen=max_reports)

    # -- sampling ----------------------------------------------------------
    def _due(self, n_closed: int) -> bool:
        if self.every_n <= 0 or n_closed <= 0 or not self._gear_pos:
            return False
        before = self._chunks_seen // self.every_n
        self._chunks_seen += n_closed
        return self._chunks_seen // self.every_n > before

    def _within_budget(self) -> bool:
        if self._spent_s == 0.0:
            return True  # first probe always runs
        elapsed = time.perf_counter() - self._born
        return self._spent_s <= self.budget_frac * max(elapsed, 1e-9)

    # -- the probe ---------------------------------------------------------
    def maybe_probe(self, batch1: dict, logits, one) -> dict | None:
        """Sample-and-measure hook; returns the report dict when a probe
        ran, else None.  Read-only on all arguments."""
        try:
            n_tok = int(batch1["tokens"].shape[-1])
            n_closed = n_tok // self._pol.buffer_size
            if not self._due(n_closed):
                return None
            if not self._within_budget():
                self._reg.get("fidelity_probe_skipped_total").inc()
                return None
            t0 = time.perf_counter()
            report = self._probe(batch1, logits, one, n_tok, n_closed)
            dt = time.perf_counter() - t0
            self._spent_s += dt
            self._reg.get("fidelity_probe_seconds").observe(dt)
            self._reg.get("fidelity_probes_total").inc()
            self.reports.append(report)
            return report
        except Exception:
            try:
                self._reg.get("fidelity_probe_errors_total").inc()
            except Exception:
                pass
            return None

    def _probe(self, batch1, logits, one, n_tok, n_closed) -> dict:
        ref_logits, ref_caches = self._ref_prefill(batch1)
        if self._fn is None:
            self._fn = self._build_fn()
        stats = self._fn(one, ref_caches)
        drift = float(jnp.max(jnp.abs(
            jnp.asarray(logits, jnp.float32).reshape(-1)
            - jnp.asarray(ref_logits, jnp.float32).reshape(-1))))
        self._reg.get("fidelity_logits_drift").observe(drift)
        layers = []
        for i in self._gear_pos:
            per_rep = {k: jax.device_get(v) for k, v in stats[i].items()}
            n_rep = len(next(iter(per_rep.values())))
            for r in range(n_rep):
                layer = r * self._n_unit + i
                row = {"layer": layer}
                for key, vals in per_rep.items():
                    row[key] = float(vals[r])
                layers.append(row)
                lab = str(layer)
                self._reg.get("fidelity_sampled_chunks_total").inc(
                    n_closed, layer=lab)
                for field in ("k", "v"):
                    self._reg.get("fidelity_rel_err").observe(
                        row[f"{field}_rel_err"], field=field, layer=lab)
                    if f"{field}_lowrank_share" in row:
                        self._reg.get("fidelity_lowrank_share").observe(
                            row[f"{field}_lowrank_share"], field=field,
                            layer=lab)
                    if f"{field}_outlier_mass" in row:
                        self._reg.get("fidelity_outlier_mass").observe(
                            row[f"{field}_outlier_mass"], field=field,
                            layer=lab)
        layers.sort(key=lambda r: r["layer"])
        return {"prompt_tokens": n_tok, "closed_chunks": n_closed,
                "logits_drift": drift, "layers": layers}

    def _build_fn(self):
        """One jitted compare program for all prompt lengths: closed-region
        masks come from the (traced) cache lengths."""
        ccfgs, pol, gear_pos = self._ccfgs, self._pol, self._gear_pos

        def per_rep(ccfg, lyr, ref):
            nb = ccfg.chunk
            n_comp = (lyr.length // nb) * nb                      # [1]
            tok = jnp.arange(ccfg.capacity)
            mask = (tok[None, :] < n_comp[:, None])[:, None, :, None]
            k_hat, v_hat = cache_lib.dense_kv(ccfg, lyr)
            k_ref = ref.k.astype(jnp.float32)
            v_ref = ref.v.astype(jnp.float32)
            out = {"k_rel_err": masked_rel_frobenius(k_hat, k_ref, mask),
                   "v_rel_err": masked_rel_frobenius(v_hat, v_ref, mask)}
            if pol.use_lowrank:
                out["k_lowrank_share"] = masked_share(
                    cache_lib._lowrank_dense(ccfg, lyr.k_a, lyr.k_b), k_hat, mask)
                out["v_lowrank_share"] = masked_share(
                    cache_lib._lowrank_dense(ccfg, lyr.v_a, lyr.v_b), v_hat, mask)
            if pol.use_sparse:
                out["k_outlier_mass"] = masked_share(
                    cache_lib._sparse_dense(ccfg, lyr.k_sp_val, lyr.k_sp_idx, "k"),
                    k_hat, mask)
                out["v_outlier_mass"] = masked_share(
                    cache_lib._sparse_dense(ccfg, lyr.v_sp_val, lyr.v_sp_idx, "v"),
                    v_hat, mask)
            return out

        @jax.jit
        def fn(one, ref_caches):
            return {i: jax.vmap(lambda lyr, ref, c=ccfgs[i]: per_rep(c, lyr, ref))(
                        one[i], ref_caches[i])
                    for i in gear_pos}

        return fn
