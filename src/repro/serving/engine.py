"""Serving engine: prefill + GEAR-cached decode, sharded over the mesh.

The engine owns the jitted prefill/decode programs (cache donated across
steps so decode is allocation-free), token sampling, and the byte-level
cache accounting the memory benchmarks read.  Two batching modes sit on
top (:mod:`repro.serving.scheduler`):

* wave mode — :meth:`Engine.generate` drives the whole batch in lockstep;
* continuous mode — the scheduler drives :meth:`Engine.decode` one step at
  a time with per-slot position vectors, and :meth:`Engine.prefill_slot`
  splices a fresh request's batch-1 cache into a live batch slot (the cache
  tree is donated, so the splice is an in-place batch-row write).

Two cache layouts (:class:`CacheLayout`):

* ``DENSE`` — every slot owns full-capacity per-slot arrays; admission is
  slot-count-limited.
* ``PAGED`` — compressed chunks live in a global pool of fixed-size pages
  addressed through per-slot block tables (DESIGN.md §5,
  :mod:`repro.serving.pagedpool`); admission is pool-bytes-limited, a
  request reserves only the pages its own lifetime needs, and prefix-cache
  hits share pages by refcount instead of copying.  Decode gathers pages
  by table index inside the fused kernel grid
  (:func:`repro.kernels.gear_decode.gear_decode_paged`), and the layout is
  bit-identical to the dense slot cache under the zero-page invariant.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core.policy import FP16, CompressionPolicy
from repro.dist import sharding as shd
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models.model import Model
from repro.models.transformer import cache_cfg_for
from repro.obs import NULL_PHASE, Observability, ObsConfig
from repro.obs.fidelity import FidelityProbe
from repro.prefixcache import PrefixCache
from repro.prefixcache import store as pc_store
from repro.serving.pagedpool import PagePool, PagePoolStore, pages_needed
from repro.serving.sampling import sample

__all__ = ["AttendPath", "PrefillMode", "CacheLayout", "EngineConfig",
           "Engine", "prefix_cache_unsupported_reason"]


class AttendPath(str, enum.Enum):
    """GEAR decode/prefill attend kernel path.

    ``AUTO`` — fused gear_attend where the cache layout supports it (Pallas
    kernel on TPU, jnp oracle elsewhere; ragged-aware, so continuous
    batching takes it too).  ``INTERPRET`` — force the Pallas kernel in
    interpret mode (CI kernel lane).  ``OFF`` — portable jnp attend.
    """
    AUTO = "auto"
    INTERPRET = "interpret"
    OFF = "off"

    __str__ = str.__str__


class PrefillMode(str, enum.Enum):
    """Prefill pipeline: ``MONOLITHIC`` (full-sequence attention, one
    batched compression event per layer) or ``STREAMING`` (chunked
    compress-as-you-go — O(compressed cache + one chunk) peak memory).
    Both build bit-identical caches."""
    MONOLITHIC = "monolithic"
    STREAMING = "streaming"

    __str__ = str.__str__


class CacheLayout(str, enum.Enum):
    """Serving cache layout: ``DENSE`` per-slot arrays or ``PAGED`` pooled
    compressed-chunk pages behind per-slot block tables (DESIGN.md §5)."""
    DENSE = "dense"
    PAGED = "paged"

    __str__ = str.__str__


def _coerce(cls, value, knob: str, options: str):
    """Enum coercion that keeps the legacy stringly error text, so existing
    callers matching on e.g. ``"prefill_mode must be"`` keep passing."""
    try:
        return cls(value)
    except ValueError:
        raise ValueError(f"{knob} must be {options}, got {value!r}") from None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    batch: int
    capacity: int                  # max total tokens per sequence
    policy: CompressionPolicy
    temperature: float = 0.0
    top_k: int = 0
    eos_id: int = -1               # -1: never stop early
    # GEAR decode-attend path (:class:`AttendPath`).  Plain strings
    # ("auto"/"interpret"/"off") are coerced for back-compat.  The same
    # knob selects the prefill kernel path (flash_prefill for monolithic
    # attention, gear_compress/gear_attend_block for streaming).
    fused: AttendPath = AttendPath.AUTO
    # Prefill pipeline (:class:`PrefillMode`); strings are coerced.
    prefill_mode: PrefillMode = PrefillMode.MONOLITHIC
    # Cross-request prefix cache (radix trie over compressed GEAR chunks,
    # repro.prefixcache): prefill_slot splices the longest cached
    # chunk-aligned prompt prefix into the slot and streams only the
    # suffix — bit-identical caches/logits vs a cold prefill.  Requires
    # prefill_mode="streaming" (the hit path attends the cached prefix in
    # compressed form, which is exactly streaming's numeric model) and a
    # model whose every layer supports the streaming pipeline.  Under the
    # PAGED layout the trie's payloads are pool page ids, so a hit is a
    # refcount bump — no chunk bytes are ever copied.
    prefix_cache: bool = False
    prefix_cache_bytes: int = 256 << 20   # trie eviction byte budget
    # Trie lifecycle: ``prefix_cache_ttl`` seconds a cached chunk stays
    # valid from insert (0 = never expires; hits do not refresh it) and
    # the budget-pressure victim policy ("lru" recency / "lfu" use count).
    # Weight swaps invalidate independently of both: Engine.set_params
    # bumps a version tag that makes every cached chunk stale at once.
    prefix_cache_ttl: float = 0.0
    prefix_cache_eviction: str = "lru"
    # Numeric quarantine: guard every request's freshly closed compressed
    # chunks against NaN/Inf before they are spliced into the shared batch
    # tree or inserted into the prefix trie.  A poisoned prefill raises
    # :class:`~repro.core.cache.NumericFault` with the shared state
    # untouched — the scheduler fails that one request (FAILED status,
    # slot reset, pages released) while co-batched slots continue
    # bit-identically.  One fused all-finite reduction over the batch-1
    # tree per prefill; set False to shave it off a trusted pipeline.
    numeric_guard: bool = True
    # Cache layout (:class:`CacheLayout`); strings are coerced.  PAGED puts
    # every GEAR-compressible attention layer's closed chunks into a global
    # page pool; window/fp16/RWKV/SSM state stays dense inside the tree.
    layout: CacheLayout = CacheLayout.DENSE
    # PAGED pool sizing — set at most one.  ``pool_pages`` is the pool's
    # page-axis length (including reserved zero page 0, matching
    # ``init_caches(..., pool_pages=...)``); ``pool_bytes`` sizes the pool
    # to a device byte budget (pages = pool_bytes // page_bytes).  Default
    # (both 0): batch * n_chunks allocatable pages — the dense-equivalent
    # worst case, useful for parity testing rather than memory savings.
    pool_pages: int = 0
    pool_bytes: int = 0
    # Observability (:class:`repro.obs.ObsConfig`): metrics registry,
    # per-request tracing, and online compression-fidelity probes.  None
    # (default) builds no telemetry state and adds zero work to the hot
    # path; ``obs=True`` coerces to ``ObsConfig()`` defaults.  See
    # docs/observability.md.
    obs: ObsConfig | None = None

    def __post_init__(self):
        if self.obs is not None and not isinstance(self.obs, ObsConfig):
            if isinstance(self.obs, bool):
                object.__setattr__(self, "obs",
                                   ObsConfig() if self.obs else None)
            elif isinstance(self.obs, dict):
                object.__setattr__(self, "obs", ObsConfig(**self.obs))
            else:
                raise ValueError(
                    f"obs must be an ObsConfig, bool, or dict, got "
                    f"{self.obs!r}")
        object.__setattr__(self, "fused", _coerce(
            AttendPath, self.fused, "fused", "auto/interpret/off"))
        object.__setattr__(self, "prefill_mode", _coerce(
            PrefillMode, self.prefill_mode, "prefill_mode",
            "monolithic/streaming"))
        object.__setattr__(self, "layout", _coerce(
            CacheLayout, self.layout, "layout", "dense/paged"))
        if self.prefix_cache and self.prefill_mode is not PrefillMode.STREAMING:
            raise ValueError(
                "prefix_cache requires prefill_mode='streaming': the hit "
                "path attends the cached prefix in compressed form, so only "
                "streaming cold prefills are bit-identical to warm ones")
        if self.prefix_cache_eviction not in ("lru", "lfu"):
            raise ValueError(
                "prefix_cache_eviction must be 'lru' or 'lfu', got "
                f"{self.prefix_cache_eviction!r}")
        if self.prefix_cache_ttl < 0:
            raise ValueError(
                f"prefix_cache_ttl must be >= 0, got {self.prefix_cache_ttl}")
        if ((self.prefix_cache_ttl or self.prefix_cache_eviction != "lru")
                and not self.prefix_cache):
            raise ValueError(
                "prefix_cache_ttl / prefix_cache_eviction require "
                "prefix_cache=True")
        if self.pool_pages and self.pool_bytes:
            raise ValueError("set pool_pages OR pool_bytes, not both")
        if self.layout is CacheLayout.DENSE and (self.pool_pages or self.pool_bytes):
            raise ValueError("pool_pages/pool_bytes only apply to layout='paged'")


def _pad_tokens(tokens, n: int, nb: int) -> np.ndarray:
    """A batch-1 prompt of ``n`` tokens right-padded to the next ``nb``
    multiple, on the host: the bucketed prefill then compiles one program
    per bucket, and no device program runs per raw length."""
    toks = np.asarray(tokens, np.int32)
    return np.pad(toks, ((0, 0), (0, -(-n // nb) * nb - n)))


def prefix_cache_unsupported_reason(cfg, policy: CompressionPolicy,
                                    capacity: int) -> str | None:
    """Why this model/policy cannot take the prefix cache (None = it can).

    The hit path replays a cached chunk-aligned prefix as compressed
    history under the streaming suffix pipeline, so every layer must (a)
    keep all its prefill state in spliceable GEAR chunks and (b) support
    streaming prefill.  RWKV / hybrid-SSM recurrent states and the VLM
    bidirectional image prefix are neither; fp16 policies have no
    compressed chunks to cache.
    """
    if policy.is_fp16:
        return "fp16 policy has no compressed chunks to cache"
    if cfg.modality != "text":
        return f"modality {cfg.modality!r} (prompt is not a flat token-id sequence)"
    if cfg.ssm and cfg.hybrid_parallel:
        return "hybrid SSM state is not chunk-decomposable"
    for kind in cfg.layer_pattern:
        if kind == "rwkv":
            return "rwkv layers carry recurrent state, not spliceable chunks"
        ccfg = cache_cfg_for(cfg, kind, policy, 1, capacity)
        if not attn_lib.streaming_prefill_supported(cfg, kind, ccfg):
            return (f"layer kind {kind!r} does not support the streaming "
                    "prefill pipeline")
    return None


class Engine:
    def __init__(self, model: Model, params: Any, ecfg: EngineConfig, mesh=None,
                 clock=None):
        self.model = model
        self.cfg = model.cfg
        self.ecfg = ecfg
        self.mesh = mesh
        self.layout = ecfg.layout
        # injectable monotonic clock shared with the prefix cache's TTL
        # logic (tests drive a FakeClock); None = real time
        self._clock = clock
        # chaos hook (serving/faults.py); attach_faults wires it + the pool
        self._faults = None
        cap = self._cap()
        # telemetry hub (repro.obs): the scheduler discovers it via
        # `engine.obs`; None when the knob is off (zero hot-path work)
        self.obs = (Observability(ecfg.obs, clock=clock)
                    if ecfg.obs is not None else None)

        if mesh is not None:
            if self.layout is CacheLayout.PAGED:
                raise NotImplementedError(
                    "paged layout is single-host for now: the block tables "
                    "are engine-owned host state (ROADMAP: sharded pool)")
            cache_abs = jax.eval_shape(
                lambda: model.init_caches(ecfg.policy, ecfg.batch, cap))
            self._cache_shard = shd.shardings_for(
                mesh, shd.cache_pspecs(self.cfg, cache_abs, mesh, ecfg.batch))
            pshard = shd.shardings_for(mesh, shd.param_pspecs(self.cfg, params, mesh))
            self.params = jax.device_put(params, pshard)
        else:
            self._cache_shard = None
            self.params = params

        # Every device program the served path dispatches is a jax.jit of a
        # function named gear_*, so a profiler trace names each module
        # (jit_gear_decode_step, ...) and device time can be read by program.
        def gear_prefill(p, b):
            return model.prefill(p, b, ecfg.policy, cap,
                                 prefill_mode=ecfg.prefill_mode,
                                 fused=ecfg.fused)

        def gear_finite_guard(tree):
            return cache_lib.tree_finite(tree)

        def gear_sample(logits, key, step, sampler):
            if step is not None:
                key = jax.random.fold_in(key, step)
            return (sampler(logits[:, -1], key, ecfg.temperature,
                            ecfg.top_k), key)

        self._prefill = jax.jit(gear_prefill)
        self._finite_fn = jax.jit(gear_finite_guard)
        self._sample = jax.jit(gear_sample, static_argnames="sampler")
        # Mixed-length serving: prefill_slot buckets a raw-length prompt up
        # to the next n_b multiple (the padded tail lands in the FP16
        # streaming buffer, never in a compressed chunk), so jit compiles
        # one program per BUCKET instead of one per distinct prompt length.
        # Gated on the same predicate as the prefix cache — bucketing rides
        # the streaming pipeline's padded-tail path, so every layer must
        # support it; other engines prefill at the exact raw length (one
        # program per length).
        self.weight_version = 0
        self._can_bucket = (
            ecfg.prefill_mode is PrefillMode.STREAMING
            and prefix_cache_unsupported_reason(self.cfg, ecfg.policy, cap)
            is None)
        if self._can_bucket:
            def gear_prefill_padded(p, b, tl):
                return model.prefill(p, b, ecfg.policy, cap,
                                     prefill_mode="streaming",
                                     fused=ecfg.fused, padded_tail=True,
                                     true_len=tl)
            self._prefill_bucketed = jax.jit(gear_prefill_padded)
        if self.layout is CacheLayout.PAGED:
            self._init_paged(cap)

            def gear_decode_step(p, tok, caches, pos, bt):
                return model.decode_step(p, tok, caches, pos, ecfg.policy,
                                         cap, fused=ecfg.fused,
                                         block_tables=bt)
        else:
            self.pool = None

            def gear_decode_step(p, tok, caches, pos):
                return model.decode_step(p, tok, caches, pos, ecfg.policy,
                                         cap, fused=ecfg.fused)
        self._decode = jax.jit(gear_decode_step, donate_argnums=(2,))
        # Slot splice: write a batch-1 cache tree over batch row `slot` of the
        # live (donated) cache.  Cache leaves are stacked [R, B, ...], so the
        # batch dim is axis 1 on every leaf (incl. RWKV/SSM states); the
        # cache pspecs keep that axis's sharding uniform across leaves, which
        # is what keeps this DUS-at-a-traced-offset legal under SPMD.
        # Two variants: the per-request prefill splice also donates the
        # batch-1 tree (freshly built each request, consumed by the row
        # write) — but a [R, 1, ...] leaf can only alias into a [R, 1, ...]
        # output, so the extra donation applies on batch-1 engines only
        # (wider geometries would just trip XLA's unusable-donation
        # warning).  reset_slot must NOT donate its batch-1 tree — that is
        # the reusable `_fresh1` zero cache.
        def gear_splice(full, one, slot):
            return cache_lib.splice_slot(full, one, slot, axis=1)

        shard_kw = ({"out_shardings": self._cache_shard}
                    if self._cache_shard is not None else {})
        self._splice = jax.jit(gear_splice, donate_argnums=(0,), **shard_kw)
        self._splice_donate_one = (
            jax.jit(gear_splice, donate_argnums=(0, 1), **shard_kw)
            if ecfg.batch == 1 else self._splice)  # identical program otherwise
        self._fresh1 = None  # lazily-built batch-1 empty cache (for reset_slot)

        self.prefix_cache = None
        if ecfg.prefix_cache:
            reason = prefix_cache_unsupported_reason(self.cfg, ecfg.policy, cap)
            if reason is not None:
                raise ValueError(f"prefix_cache unsupported here: {reason}")
            store = (PagePoolStore(self.pool)
                     if self.layout is CacheLayout.PAGED else None)
            self.prefix_cache = PrefixCache(ecfg.policy.buffer_size,
                                            ecfg.prefix_cache_bytes, store=store,
                                            ttl=ecfg.prefix_cache_ttl,
                                            eviction=ecfg.prefix_cache_eviction,
                                            clock=self._clock,
                                            validate=ecfg.numeric_guard)
            self._cache_cfgs = [cache_cfg_for(self.cfg, kind, ecfg.policy, 1, cap)
                                for kind in self.cfg.layer_pattern]
            # per-shape jitted programs for the hit path, keyed by the
            # cached-prefix chunk count (suffix prefill; plus a padded-tail
            # flag for bucketed suffixes) and extraction chunk range —
            # length bucketing means only a handful of shapes ever occur;
            # jitting them matters because the eager versions pay one
            # dispatch per cache field per chunk.  The scaffold splice
            # needs no key: its trace depends only on the payload pytree
            # structure, which jit re-specializes on by itself.
            self._suffix_fns: dict[tuple[int, bool], Any] = {}
            self._extract_fns: dict[tuple[int, int], Any] = {}
            def gear_prefix_splice(fresh, payloads):
                return pc_store.splice_tree_chunks(self._cache_cfgs, fresh,
                                                   0, payloads)
            self._splice_prefix = jax.jit(gear_prefix_splice)

        # online compression-fidelity probes (repro.obs.fidelity): an fp16
        # shadow prefill of sampled prompts is the exact reference the
        # streaming pipeline discarded; the probe reads each sampled
        # request's batch-1 tree BEFORE the donating splice, so it can
        # never perturb serving state (probe-parity sweep in
        # tests/test_cache.py).  GEAR engines on text models only — other
        # modalities/policies have nothing to compare.
        if (self.obs is not None and ecfg.obs.fidelity_every_n > 0
                and not ecfg.policy.is_fp16 and self.cfg.modality == "text"):
            def gear_fidelity_prefill(p, b):
                return model.prefill(p, b, FP16, cap)
            ref_jit = jax.jit(gear_fidelity_prefill)
            self.obs.fidelity = FidelityProbe(
                ref_prefill=lambda b: ref_jit(self.params, b),
                cache_cfgs=[None if kind == "rwkv"
                            else cache_cfg_for(self.cfg, kind, ecfg.policy,
                                               1, cap)
                            for kind in self.cfg.layer_pattern],
                policy=ecfg.policy, registry=self.obs.registry,
                every_n=ecfg.obs.fidelity_every_n,
                budget_frac=ecfg.obs.fidelity_budget_frac)

    # -- paged-layout setup --------------------------------------------
    def _init_paged(self, cap: int) -> None:
        ecfg = self.ecfg
        if ecfg.policy.is_fp16:
            raise ValueError(
                "paged layout requires a compressed (GEAR) policy: fp16 "
                "caches have no chunk pages to pool")
        if self.cfg.ssm and self.cfg.hybrid_parallel:
            raise NotImplementedError(
                "hybrid SSM recurrent state is not chunk-decomposable; "
                "serve it with layout='dense'")
        nb = ecfg.policy.buffer_size
        self._n_chunks = cap // nb
        # batch-1 per-position cache configs; which positions are pooled
        # mirrors transformer._unit_cache exactly (window/fp16/rwkv dense)
        self._pos_cfgs1 = [
            None if kind == "rwkv"
            else cache_cfg_for(self.cfg, kind, ecfg.policy, 1, cap)
            for kind in self.cfg.layer_pattern]
        self._paged_flags = [
            ccfg is not None and cache_lib.paged_supported(ccfg)
            for ccfg in self._pos_cfgs1]
        if not any(self._paged_flags):
            raise ValueError(
                "paged layout: no GEAR-compressible attention layer in "
                f"pattern {self.cfg.layer_pattern!r}")
        # one page = one chunk across the WHOLE model: R repeats of every
        # pooled position contribute their per-layer page cost
        R = self.cfg.pattern_repeats
        self._page_bytes = R * sum(
            cache_lib.page_nbytes(ccfg)
            for ccfg, flag in zip(self._pos_cfgs1, self._paged_flags) if flag)
        if ecfg.pool_pages:
            n_pages = ecfg.pool_pages
        elif ecfg.pool_bytes:
            n_pages = ecfg.pool_bytes // self._page_bytes + 1
        else:
            n_pages = ecfg.batch * self._n_chunks + 1   # dense-equivalent
        if n_pages < 2:
            raise ValueError(
                f"pool of {n_pages} pages cannot hold page 0 + one chunk "
                f"(page_bytes={self._page_bytes}; raise pool_bytes/pool_pages)")
        self._n_pages = n_pages
        self._paged_splice_fns: dict[int, Any] = {}
        self._new_pool()

    def _new_pool(self) -> None:
        """Fresh allocator + device block table (and, because trie payloads
        are page ids into the pool being discarded, a fresh prefix trie)."""
        self.pool = PagePool(self._n_pages, self.ecfg.batch, self._n_chunks,
                             self._page_bytes)
        self.pool.faults = self._faults
        self._bt = jnp.asarray(self.pool.block_tables)
        if getattr(self, "prefix_cache", None) is not None:
            self.prefix_cache = PrefixCache(self.ecfg.policy.buffer_size,
                                            self.ecfg.prefix_cache_bytes,
                                            store=PagePoolStore(self.pool),
                                            ttl=self.ecfg.prefix_cache_ttl,
                                            eviction=self.ecfg.prefix_cache_eviction,
                                            clock=self._clock)

    def _cap(self) -> int:
        nb = self.ecfg.policy.buffer_size
        return (self.ecfg.capacity + nb - 1) // nb * nb

    @property
    def attend_path(self) -> str:
        """Decode-attend path compiled into this engine's attention layers,
        named for what runs: "fused-kernel" (gear_attend through the Pallas
        kernel — the TPU path), "fused-oracle" (gear_attend's jnp oracle —
        ``fused="auto"`` off the TPU), "fused-interpret" (kernel forced in
        interpret mode), or "xla" (no layer qualifies: fp16/window caches,
        unsupported layouts, or ``fused="off"``).  Checks every kind in the
        model's layer pattern —
        local/window layers never fuse, so a model needs at least one
        GEAR-layout attention layer to report a fused path.  The paged
        layout shares the dense kernel constraint (the paged kernel gathers
        pages by block-table index but runs the same compute body)."""
        fused_any = any(
            kernel_ops.fused_supported(cache_cfg_for(
                self.cfg, kind, self.ecfg.policy, self.ecfg.batch, self._cap()))
            for kind in self.cfg.layer_pattern if kind != "rwkv")
        if self.ecfg.fused is AttendPath.OFF or not fused_any:
            return "xla"
        if self.ecfg.fused is AttendPath.INTERPRET:
            return "fused-interpret"
        return "fused-kernel" if kernel_ops.on_tpu() else "fused-oracle"

    @property
    def decode_kernel_grid(self) -> tuple[int, int, int] | None:
        """(chunk, capacity chunks, rows a slot): the GEAR decode kernel's
        grid per slot, each row walking the capacity's chunks — one row per
        KV head for the dense layout's ``gear_decode``, one row for the
        paged layout's ``gear_decode_paged`` (a grid step takes all of a
        slot's heads); None when no decode layer runs it (``attend_path``
        "xla")."""
        if self.attend_path == "xla":
            return None
        nb = self.ecfg.policy.buffer_size
        rows = 1 if self.layout is CacheLayout.PAGED else self.cfg.num_kv_heads
        return nb, self._cap() // nb, rows

    # ------------------------------------------------------------------
    def attach_faults(self, injector) -> None:
        """Wire a :class:`~repro.serving.faults.FaultInjector` into the
        engine's chaos hooks (prefill corruption here, admission faults in
        the page pool).  ``None`` detaches.  Production never calls this —
        the scheduler does, when constructed with ``faults=...``."""
        self._faults = injector
        if self.pool is not None:
            self.pool.faults = injector
        if injector is not None:
            injector.obs = self.obs

    def _guard_one(self, one):
        """Numeric quarantine boundary for one request's batch-1 cache tree.

        Runs after the (cold or suffix) prefill and before anything shares
        the result — the batched splice, the trie insert, the page
        scatter.  The chaos injector's NaN corruption lands here too, so
        an injected poisoned chunk takes exactly the path a real one
        would.  Raises :class:`~repro.core.cache.NumericFault` with all
        shared state untouched; read-only otherwise (bit-identity safe).
        """
        if self._faults is not None:
            one = self._faults.corrupt_tree(one)
        if not self.ecfg.numeric_guard:
            return one
        with self._phase("gear.guard"):
            finite = bool(self._finite_fn(one))
        if not finite:
            if self.obs is not None:
                self.obs.quarantine()
                self.obs.tracer.event_bound("quarantine")
            raise cache_lib.NumericFault(
                "prefill produced NaN/Inf in a compressed chunk; "
                "quarantining this request (shared cache state untouched)")
        return one

    # -- observability hooks -------------------------------------------
    def _phase(self, name: str):
        """Step phase on the engine's telemetry (``ObsConfig.profiler``);
        a no-op without obs."""
        return NULL_PHASE if self.obs is None else self.obs.phase(name)

    def _span(self, name: str):
        """Trace span on the scheduler-bound rid; no-op without obs."""
        if self.obs is None:
            return contextlib.nullcontext()
        return self.obs.tracer.span_bound(name)

    def _obs_prefill(self, batch1, logits, one, n_hit: int = 0,
                     pages_reserved: int | None = None) -> None:
        """Per-prefill telemetry, called with the guarded batch-1 tree
        BEFORE the donating splice: annotates the scheduler's open prefill
        span (prefix hit / bucket / pages), feeds the bucket histogram,
        and hands the read-only tree to the fidelity probe."""
        o = self.obs
        if o is None:
            return
        plen = int(batch1["tokens"].shape[-1])
        nb = self.ecfg.policy.buffer_size
        bucket = (plen + nb - 1) // nb * nb if self._can_bucket else plen
        o.observe_bucket(bucket)
        ann = {"prompt_tokens": plen, "bucket_tokens": bucket,
               "prefix_hit_chunks": n_hit}
        if pages_reserved is not None:
            ann["pages_reserved"] = pages_reserved
        o.tracer.annotate(**ann)
        if o.fidelity is not None:
            o.fidelity.maybe_probe(batch1, logits, one)

    def audit(self) -> dict:
        """Cross-structure invariant audit: page pool refcounts against
        block tables + live trie handles, plus the trie's own structural
        audit.  Returns ``{"ok", "issues", ...}``; never raises — the
        chaos suite asserts on it after every fault schedule."""
        issues: list[str] = []
        report: dict[str, Any] = {}
        if self.pool is not None:
            retained = None
            if self.prefix_cache is not None:
                retained = ([int(h) for h in self.prefix_cache.live_handles()]
                            + [int(h) for h in self.prefix_cache.trie.pending_free])
            report["pool"] = self.pool.audit(retained=retained)
            issues += [f"pool: {m}" for m in report["pool"]["issues"]]
        if self.prefix_cache is not None:
            report["trie"] = self.prefix_cache.audit()
            issues += [f"trie: {m}" for m in report["trie"]["issues"]]
        return {"ok": not issues, "issues": issues, **report}

    # ------------------------------------------------------------------
    def set_params(self, params: Any) -> None:
        """Swap the served weights (hot reload / fine-tune push).

        Bumps :attr:`weight_version` and invalidates every prefix-cache
        entry: cached chunks were compressed under the OLD weights, so
        splicing them into a new-weights prefill would silently serve
        stale activations.  The trie prunes lazily — the counters show up
        as ``version_evictions`` in :attr:`PrefixCache.stats`.
        """
        if self.mesh is not None:
            pshard = shd.shardings_for(
                self.mesh, shd.param_pspecs(self.cfg, params, self.mesh))
            params = jax.device_put(params, pshard)
        self.params = params
        self.weight_version += 1
        if self.prefix_cache is not None:
            self.prefix_cache.bump_version()

    # ------------------------------------------------------------------
    def mesh_context(self):
        """Context the engine's model programs trace and run under: the
        engine's mesh as JAX's context mesh, which is how the kernel
        wrappers (:mod:`repro.kernels.ops`) find the head shards to run
        each Pallas kernel on.  A no-op without a mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def compiled_decode_text(self) -> str:
        """Optimized HLO of this engine's decode step, compiled for its own
        devices and operand layouts (abstract operands — nothing is
        allocated).  Tells a caller which kernels the step really runs:
        a Pallas kernel shows up as a ``tpu_custom_call``."""
        like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=x.sharding)
        ecfg, cap = self.ecfg, self._cap()
        if self.layout is CacheLayout.PAGED:
            caches = jax.eval_shape(lambda: self.model.init_caches(
                ecfg.policy, ecfg.batch, cap, layout="paged",
                pool_pages=self._n_pages))
        else:
            caches = jax.eval_shape(lambda: self.model.init_caches(
                ecfg.policy, ecfg.batch, cap))
            if self._cache_shard is not None:
                caches = jax.tree.map(
                    lambda c, s: jax.ShapeDtypeStruct(c.shape, c.dtype,
                                                      sharding=s),
                    caches, self._cache_shard)
        args = (jax.tree.map(like, self.params),
                {"tokens": jax.ShapeDtypeStruct((ecfg.batch, 1), jnp.int32)},
                caches, jax.ShapeDtypeStruct((ecfg.batch,), jnp.int32))
        if self.layout is CacheLayout.PAGED:
            args += (jax.ShapeDtypeStruct(self._bt.shape, self._bt.dtype),)
        with self.mesh_context():
            return self._decode.lower(*args).compile().as_text()

    def prefill(self, batch: dict):
        if self.layout is CacheLayout.PAGED:
            raise NotImplementedError(
                "full-batch wave prefill is dense-only; paged engines serve "
                "through Engine.prefill_slot / Scheduler.run_continuous")
        with self.mesh_context():
            logits, caches = self._prefill(self.params, batch)
        if self._cache_shard is not None:
            caches = jax.device_put(caches, self._cache_shard)
        return logits, caches

    def _cold_prefill(self, batch1: dict):
        """Batch-1 prompt prefill at bucketed length.

        A prompt whose raw length is not an ``n_b`` multiple is right-padded
        to the next bucket and run through the padded-tail streaming
        pipeline (pad tokens never reach compressed storage; cache lengths
        and logits reflect the raw length), so jit compiles one program per
        bucket.  Aligned prompts — and engines that cannot bucket — take
        the plain prefill program at the exact length.
        """
        n = batch1["tokens"].shape[1]
        nb = self.ecfg.policy.buffer_size
        with self._phase("gear.prefill"), self.mesh_context():
            if not self._can_bucket or n % nb == 0:
                return self._prefill(self.params, batch1)
            padded = {"tokens": _pad_tokens(batch1["tokens"], n, nb)}
            return self._prefill_bucketed(self.params, padded, np.int32(n))

    def decode(self, token_batch: dict, caches, pos):
        """One decode step.  ``pos``: scalar or per-slot [B] int32 vector."""
        with self._phase("gear.decode"), self.mesh_context():
            if self.layout is CacheLayout.PAGED:
                return self._decode(self.params, token_batch, caches,
                                    jnp.asarray(pos, jnp.int32), self._bt)
            return self._decode(self.params, token_batch, caches,
                                jnp.asarray(pos, jnp.int32))

    def sample_next(self, logits, key, step=None, sampler=sample):
        """Next token of each row from ``logits``' last position, and the
        key it was drawn with: ``key`` folded with ``step`` (a decode step)
        or ``key`` itself (``step=None``: a prefill's first token).
        ``sampler`` is the token rule (``repro.serving.sampling.sample``'s
        signature; the scheduler passes its own).  One program
        (``gear_sample``) per rule; the tokens stay on the device."""
        return self._sample(logits, key, step, sampler=sampler)

    # -- slot-level continuous batching --------------------------------
    def prefill_slot(self, batch1: dict, caches, slot: int, admit: bool = True,
                     reserve_tokens: int | None = None):
        """Prefill ONE request (batch-1 inputs) and splice it into ``slot``.

        Returns (logits [1, 1, ...] for the request's last prompt position,
        new caches).  The batch-1 prefill is bit-identical to a solo run of
        the same prompt, so a spliced request decodes exactly as it would
        alone (DESIGN.md §splice isolation).  Both the live ``caches`` tree
        and the request's batch-1 tree are donated into the splice, so the
        per-request path is one batch-row write with no tree copies.  With
        ``prefill_mode="streaming"`` the batch-1 prefill never materializes
        the prompt's FP16 K/V, so long-prompt splices stay within the
        compressed-cache memory budget.

        ``batch1`` carries the RAW prompt (no scheduler padding).  Prompts
        whose length is not an ``n_b`` multiple are length-bucketed: padded
        up to the next chunk multiple and run through the padded-tail
        streaming pipeline, so jit compiles one program per bucket while
        cache lengths, logits, and trie keys all reflect the true length
        (engines that cannot take the streaming pipeline prefill at the
        exact raw length instead — one compile per distinct length).

        With ``EngineConfig.prefix_cache`` on, the trie is consulted first:
        the longest cached chunk-aligned prefix of the raw prompt is
        spliced straight into a batch-1 cache tree and only the remaining
        suffix runs streaming prefill (bucketed the same way), with the
        prefix visible as already-compressed history — bit-identical caches
        and logits vs the cold bucketed path (DESIGN.md §4).  ``admit`` is
        the scheduler's admission policy: when True the prompt's newly
        closed chunks are inserted back into the trie after prefill — only
        FULL ``n_b``-token chunks of real tokens close, so pad garbage
        never enters the trie.

        PAGED layout: the slot first reserves its lifetime's pages from the
        pool — ``reserve_tokens`` (prompt + generation budget; defaults to
        full capacity) right-sizes the reservation, which is where paged
        concurrency comes from.  Prefix-cache hits arrive as shared page
        ids (refcount bump, no copy); fresh pages are zeroed before the
        block-table row exposes them and the prompt's closed chunks are
        scattered in.  Raises :class:`~repro.serving.pagedpool.PoolExhausted`
        — with no device work done — when the pool cannot cover the
        reservation; the scheduler queues and retries.
        """
        if self.layout is CacheLayout.PAGED:
            return self._prefill_slot_paged(batch1, caches, slot, admit,
                                            reserve_tokens)
        if self.prefix_cache is None:
            logits, one = self._cold_prefill(batch1)
            one = self._guard_one(one)
            self._obs_prefill(batch1, logits, one)
            with self._span("splice"):
                return logits, self._splice_donate_one(
                    caches, one, np.int32(slot))
        tokens = np.asarray(batch1["tokens"][0])
        nb = self.ecfg.policy.buffer_size
        n = tokens.shape[0]
        # always leave >= 1 suffix token so prefill computes the
        # last-position logits the first sampled token comes from
        match = self.prefix_cache.match(tokens, max_chunks=max((n - 1) // nb, 0))
        n_hit = match.n_chunks
        try:
            if n_hit:
                one1 = self._splice_prefix(self._fresh_batch1(),
                                           match.payloads)
                logits, one = self._prefill_suffix(tokens, n_hit, one1)
            else:
                logits, one = self._cold_prefill(batch1)
            one = self._guard_one(one)
            self._obs_prefill(batch1, logits, one, n_hit=n_hit)
            if admit and n // nb > n_hit:
                payloads = self._extract_fn(n_hit, n // nb)(one)
                self.prefix_cache.insert(tokens, payloads, start_chunk=n_hit)
        finally:
            self.prefix_cache.release(match)
        with self._span("splice"):
            return logits, self._splice_donate_one(
                caches, one, np.int32(slot))

    def _prefill_suffix(self, tokens: np.ndarray, n_hit: int, one1):
        """Run the (possibly bucketed) suffix after an ``n_hit``-chunk trie
        hit over the spliced batch-1 scaffold ``one1``."""
        nb = self.ecfg.policy.buffer_size
        suf = np.asarray(tokens[n_hit * nb:], np.int32)
        n_suf = suf.shape[0]
        with self._phase("gear.prefill_suffix"), self.mesh_context():
            if n_suf % nb == 0:
                return self._suffix_fn(n_hit)(self.params,
                                              {"tokens": suf[None]}, one1)
            padded = {"tokens": _pad_tokens(suf[None], n_suf, nb)}
            return self._suffix_fn(n_hit, padded_tail=True)(
                self.params, padded, one1, np.int32(n_suf))

    def _prefill_slot_paged(self, batch1, caches, slot, admit, reserve_tokens):
        nb = self.ecfg.policy.buffer_size
        cap = self._cap()
        plen = self._prompt_len(batch1)
        n_closed = plen // nb                 # chunks the prompt closes
        reserve = cap if reserve_tokens is None else min(int(reserve_tokens), cap)
        n_total = max(pages_needed(max(reserve, plen), nb), n_closed)

        match, n_hit, shared = None, 0, []
        if self.prefix_cache is not None:
            tokens = np.asarray(batch1["tokens"][0])
            match = self.prefix_cache.match(
                tokens, max_chunks=max((plen - 1) // nb, 0))
            n_hit = match.n_chunks
            shared = [int(p) for p in match.payloads]   # payloads ARE page ids
        try:
            # splicing over a live slot discards its previous request (the
            # dense layout overwrites the row; here we release its pages)
            if self.pool.slot_pages(slot).size:
                self.pool.release_slot(slot)
            # host-side reservation FIRST — PoolExhausted costs no device work
            fresh = self.pool.admit(slot, n_total, shared=shared)
            try:
                if n_hit:
                    one1 = self._gather_scaffold(
                        caches, self._fresh_batch1(),
                        jnp.asarray(shared, jnp.int32))
                    logits, one = self._prefill_suffix(tokens, n_hit, one1)
                else:
                    logits, one = self._cold_prefill(batch1)
                # quarantine BEFORE the donating splice: on failure the live
                # tree is untouched and the reservation rolls back below
                one = self._guard_one(one)
            except BaseException:
                self.pool.release_slot(slot)
                self._bt = jnp.asarray(self.pool.block_tables)
                raise
            self._obs_prefill(batch1, logits, one, n_hit=n_hit,
                              pages_reserved=n_total)
            n_sc = n_closed - n_hit
            with self._span("splice"), self._phase("gear.splice"):
                caches = self._paged_splice_fn(n_hit)(
                    caches, one,
                    np.asarray(fresh[n_sc:], np.int32),    # reserved: zero
                    np.asarray(fresh[:n_sc], np.int32),    # closed: scatter
                    np.int32(slot))
                self._bt = jnp.asarray(self.pool.block_tables)
            if self.prefix_cache is not None and admit and n_closed > n_hit:
                row = self.pool.block_tables[slot]
                self.prefix_cache.insert(
                    tokens, [int(p) for p in row[n_hit:n_closed]],
                    start_chunk=n_hit)
        finally:
            if match is not None:
                self.prefix_cache.release(match)
        return logits, caches

    def _paged_splice_fn(self, c_lo: int):
        """Jitted paged slot splice: zero the slot's reserved pages, scatter
        the batch-1 prefill's closed chunks ``[c_lo, c_lo + n_sc)`` into its
        fresh pages, and row-write the streaming buffer / length (dense
        positions in a mixed tree splice whole, as before).  Keyed by the
        prefix chunk offset; jit re-specializes on the page-count shapes."""
        fn = self._paged_splice_fns.get(c_lo)
        if fn is None:
            def gear_paged_splice(caches, one, zero_pages, sc_pages, slot):
                n_sc = sc_pages.shape[0]
                out = []
                for i, flag in enumerate(self._paged_flags):
                    if not flag:
                        out.append(cache_lib.splice_slot(
                            caches[i], one[i], slot, axis=1))
                        continue
                    ccfg1 = self._pos_cfgs1[i]

                    def upd(lyr, one_lyr, ccfg1=ccfg1):
                        lyr = cache_lib.zero_pool_pages(ccfg1, lyr, zero_pages)
                        if n_sc:
                            chunks = cache_lib.extract_prefix_chunks(
                                ccfg1, one_lyr, n_sc, c_lo)
                            lyr = cache_lib.scatter_pool_chunks(
                                ccfg1, lyr, sc_pages, chunks)
                        return lyr

                    lyr = jax.vmap(upd)(caches[i], one[i])   # over repeats R
                    sub = cache_lib.splice_slot(
                        {"buf_k": lyr.buf_k, "buf_v": lyr.buf_v,
                         "length": lyr.length},
                        {"buf_k": one[i].buf_k, "buf_v": one[i].buf_v,
                         "length": one[i].length},
                        slot, axis=1)
                    out.append(dataclasses.replace(lyr, **sub))
                return tuple(out)

            fn = jax.jit(gear_paged_splice, donate_argnums=(0,))
            self._paged_splice_fns[c_lo] = fn
        return fn

    def _gather_scaffold_impl(self, caches, fresh, pages):
        """Trace: gather prefix pages out of the pool into the batch-1 dense
        scaffold the suffix prefill runs over — the paged twin of the dense
        engine's host-payload ``_splice_prefix``."""
        n_hit = pages.shape[0]
        per_pos = []
        for i, flag in enumerate(self._paged_flags):
            ccfg1 = self._pos_cfgs1[i]
            per_pos.append(jax.vmap(
                lambda lyr, ccfg1=ccfg1: cache_lib.gather_pool_chunks(
                    ccfg1, lyr, pages))(caches[i]))
        payloads = [tuple(p[c] for p in per_pos) for c in range(n_hit)]
        return pc_store.splice_tree_chunks(self._cache_cfgs, fresh, 0, payloads)

    def _gather_scaffold(self, caches, fresh, pages):
        # prefix_cache requires every layer paged-capable, so per_pos covers
        # all positions; jit re-specializes per distinct page count
        if not hasattr(self, "_gather_fn"):
            def gear_prefix_gather(caches, fresh, pages):
                return self._gather_scaffold_impl(caches, fresh, pages)
            self._gather_fn = jax.jit(gear_prefix_gather)
        return self._gather_fn(caches, fresh, pages)

    def _fresh_batch1(self):
        """Memoized empty batch-1 cache tree (read-only — splices copy out
        of it; never donate it into a jitted program)."""
        if self._fresh1 is None:
            self._fresh1 = self.model.init_caches(self.ecfg.policy, 1, self._cap())
        return self._fresh1

    def _suffix_fn(self, n_pre_chunks: int, padded_tail: bool = False):
        """Jitted suffix prefill for a ``n_pre_chunks``-chunk cached prefix.

        The prefix length is static (it fixes every array shape in the
        suffix pipeline), so programs are compiled per distinct chunk
        count; ``padded_tail=True`` is the bucketed-suffix variant, which
        additionally takes the traced true suffix length (jit then
        re-specializes per bucket width on top).  The scaffold tree is NOT
        donated: the streaming store path assembles each cache array from
        the stacked compression-scan outputs, so XLA cannot alias any
        input leaf into its output (every leaf would trip the
        unusable-donation warning) — and the un-donated scaffold may alias
        the memoized ``_fresh_batch1`` tree's buffer/length leaves safely.
        """
        fn = self._suffix_fns.get((n_pre_chunks, padded_tail))
        if fn is None:
            start = n_pre_chunks * self.ecfg.policy.buffer_size
            if padded_tail:
                def gear_prefill_suffix_padded(p, b, c1, tl):
                    return self.model.prefill_suffix(
                        p, b, c1, start, self.ecfg.policy, self._cap(),
                        fused=self.ecfg.fused, padded_tail=True, true_len=tl)
                fn = jax.jit(gear_prefill_suffix_padded)
            else:
                def gear_prefill_suffix(p, b, c1):
                    return self.model.prefill_suffix(
                        p, b, c1, start, self.ecfg.policy, self._cap(),
                        fused=self.ecfg.fused)
                fn = jax.jit(gear_prefill_suffix)
            self._suffix_fns[(n_pre_chunks, padded_tail)] = fn
        return fn

    def _extract_fn(self, c_lo: int, c_hi: int):
        """Jitted chunk extraction from a batch-1 cache tree."""
        fn = self._extract_fns.get((c_lo, c_hi))
        if fn is None:
            def gear_prefix_extract(caches):
                return pc_store.extract_tree_chunks(self._cache_cfgs, caches,
                                                    c_lo, c_hi)
            fn = jax.jit(gear_prefix_extract)
            self._extract_fns[(c_lo, c_hi)] = fn
        return fn

    def reset_slot(self, caches, slot: int):
        """Return ``caches`` with batch row ``slot`` cleared to empty state.

        PAGED: releases the slot's block-table row back to the pool (pure
        host refcounting — freed pages are re-zeroed at their NEXT
        admission, so release does no device work beyond the buffer/length
        row clear)."""
        if self.layout is CacheLayout.PAGED:
            self.pool.release_slot(slot)
            self._bt = jnp.asarray(self.pool.block_tables)
            if not hasattr(self, "_paged_reset_fn"):
                def gear_paged_reset(caches, fresh1, slot):
                    out = []
                    for i, flag in enumerate(self._paged_flags):
                        if not flag:
                            out.append(cache_lib.splice_slot(
                                caches[i], fresh1[i], slot, axis=1))
                            continue
                        sub = cache_lib.splice_slot(
                            {"buf_k": caches[i].buf_k, "buf_v": caches[i].buf_v,
                             "length": caches[i].length},
                            {"buf_k": fresh1[i].buf_k, "buf_v": fresh1[i].buf_v,
                             "length": fresh1[i].length},
                            slot, axis=1)
                        out.append(dataclasses.replace(caches[i], **sub))
                    return tuple(out)
                self._paged_reset_fn = jax.jit(gear_paged_reset,
                                               donate_argnums=(0,))
            return self._paged_reset_fn(caches, self._fresh_batch1(),
                                        np.int32(slot))
        return self._splice(caches, self._fresh_batch1(),
                            np.int32(slot))

    def reclaim_pages(self, n_pages: int) -> int:
        """Evict prefix-trie entries until ``n_pages`` pool pages came free
        (or nothing evictable remains).  The scheduler's deadlock valve:
        with every slot idle, the only references keeping pages off the
        free list are the trie's.  Returns pages actually reclaimed."""
        if self.pool is None or self.prefix_cache is None:
            return 0
        freed = self.prefix_cache.evict_bytes(n_pages * self.pool.page_bytes)
        return freed // self.pool.page_bytes

    # ------------------------------------------------------------------
    def generate(self, batch: dict, max_new_tokens: int, key=None, active=None):
        """Greedy/sampled wave generation.  Returns (tokens [B, T], stats).

        ``active``: optional bool mask [B] of slots holding real requests;
        padded copy slots are excluded from the throughput accounting.
        Dense-layout only — paged engines serve through continuous batching
        (:meth:`repro.serving.scheduler.Scheduler.run_continuous`).
        """
        key = key if key is not None else jax.random.PRNGKey(0)
        cfg, ecfg = self.cfg, self.ecfg
        t0 = time.time()
        logits, caches = self.prefill(batch)
        t_prefill = time.time() - t0
        prompt_len = self._prompt_len(batch)
        B = logits.shape[0]

        tok, key = self.sample_next(logits, key)
        out = [tok]
        done = jnp.zeros(tok.shape[:1], bool)
        t1 = time.time()
        for t in range(max_new_tokens - 1):
            tb = {"tokens": tok[:, None] if cfg.modality != "audio" else tok[:, None, :]}
            # per-slot position vector: the same decode program serves the
            # continuous-batching path, where positions genuinely differ.
            pos = jnp.full((B,), prompt_len + t, jnp.int32)
            logits, caches = self.decode(tb, caches, pos)
            tok, key = self.sample_next(logits, key, t)
            if ecfg.eos_id >= 0:
                done = done | (tok == ecfg.eos_id) if cfg.modality != "audio" else done
                tok = jnp.where(done, ecfg.eos_id, tok) if cfg.modality != "audio" else tok
            out.append(tok)
            if ecfg.eos_id >= 0 and bool(done.all()):
                break
        toks = jnp.stack(out, axis=1)
        t_decode = time.time() - t1
        stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_tok_per_s": self._decode_tok_per_s(toks, t_decode, active),
            "cache_bytes": self.cache_nbytes(caches),
        }
        return toks, stats

    def _decode_tok_per_s(self, toks, t_decode: float, active) -> float:
        """Decode throughput over USEFUL tokens only: padded copy slots
        (``active`` False) and post-EOS / early-exit filler are excluded, so
        bench numbers aren't inflated by throwaway work."""
        tnp = np.asarray(toks)
        B, T = tnp.shape[0], tnp.shape[1]
        act = np.ones(B, bool) if active is None else np.asarray(active, bool)
        n_use = np.full(B, T)
        if self.ecfg.eos_id >= 0 and self.cfg.modality != "audio":
            hit = tnp == self.ecfg.eos_id
            has = hit.any(axis=1)
            n_use[has] = hit.argmax(axis=1)[has] + 1  # keep the EOS itself
        useful_decode = int(np.maximum(n_use - 1, 0)[act].sum())  # 1st tok = prefill
        return useful_decode / max(t_decode, 1e-9)

    def _prompt_len(self, batch) -> int:
        n = batch["tokens"].shape[1]
        if self.cfg.modality == "vlm":
            n += self.cfg.num_prefix_tokens
        return n

    def init_caches(self):
        if self.layout is CacheLayout.PAGED:
            # a fresh tree zeroes the pool device-side, so the allocator
            # (and the trie, whose payloads are ids into the old pool)
            # must restart with it
            self._new_pool()
            return self.model.init_caches(self.ecfg.policy, self.ecfg.batch,
                                          self._cap(), layout="paged",
                                          pool_pages=self._n_pages)
        caches = self.model.init_caches(self.ecfg.policy, self.ecfg.batch, self._cap())
        if self._cache_shard is not None:
            caches = jax.device_put(caches, self._cache_shard)
        return caches

    def new_view(self):
        """Blessed slot-API facade over a fresh cache tree
        (:class:`repro.serving.views.CacheView`): the scheduler drives the
        view instead of threading raw trees through free functions."""
        from repro.serving.views import DenseCacheView, PagedCacheView
        caches = self.init_caches()
        if self.layout is CacheLayout.PAGED:
            return PagedCacheView(self, caches)
        return DenseCacheView(self, caches)

    @staticmethod
    def cache_nbytes(caches) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
