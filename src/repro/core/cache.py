"""Static-shape GEAR-compressed KV cache with streaming buffer.

This is the serving-engine representation of the paper's Algorithm 1 under
XLA's static-shape constraint:

* The cache is divided into **chunks** of ``n_b`` tokens (= the streaming
  buffer size).  Newly decoded tokens land in an FP16 ring buffer; once the
  buffer holds ``n_b`` tokens it is compressed as one chunk (quant backbone +
  per-chunk low-rank factors + per-chunk outliers) and written into the
  packed arrays at its chunk index — a ``lax.cond`` keeps the whole decode
  step a single XLA program.
* Prefill compresses ``n // n_b`` chunks in one batched call (leading-dim
  batching of :func:`repro.core.gear.compress_matrix`), leftover tokens go to
  the buffer.
* Attention never materializes the FP16 cache: scores are computed from the
  packed codes via the identity ``q·K̂ᵀ = (q⊙scale)·codesᵀ + q·zero`` (for
  per-channel K quant), the low-rank path is evaluated factored
  (``(q·B_c)·A_cᵀ``, the paper's separate-path trick), and outliers are
  applied per-chunk.  The Pallas kernel (:mod:`repro.kernels.gear_decode`)
  fuses the same math; this module is the jnp reference/portable path.

Shapes (H = kv heads, S = capacity, C = S/n_b chunks, r = policy.rank,
per = 32 // bits packed lanes):

  k_packed  int32 [B, H, S, Dh/per]      v_packed  int32 [B, H, S, Dh/per]
  k_scale   bf16  [B, H, Ck, Dh]         v_scale   bf16  [B, H, S, Gv]
  k_zero            (same as k_scale)    v_zero            (same as v_scale)
  k_a       bf16  [B, H, S, r]           v_a       bf16  [B, H, S, r]
  k_b       bf16  [B, H, C, Dh, r]       v_b       bf16  [B, H, C, Dh, r]
  k_sp_val  bf16  [B, H, C, Dh, 2ks]     v_sp_val  bf16  [B, H, S, 2kv]
  k_sp_idx  int32   (same)               v_sp_idx  int32   (same)
  buf_k/buf_v bf16 [B, H, n_b, Dh]       length    int32 [B]

(for the per-token-group baseline backbone K uses the V layout.)

**Per-slot state.**  ``length`` (and the window cache's ``pos``) carry a
leading batch dim: every batch row is an independent *slot* that may hold a
different request at a different phase of its life.  All decode-time writes
(:func:`append_token`) address each slot at its own offset, and all attend
masks are per-slot — this is what makes slot-level continuous batching
(:func:`prefill_into_slot` / :func:`reset_slot` / :func:`splice_slot`) a pure
batch-dim operation.  The slot-splice protocol is specified in DESIGN.md.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import gear as gear_lib
from repro.core import lowrank as lr_lib
from repro.core import outlier as ol_lib
from repro.core import packing
from repro.core import quant as q_lib
from repro.core.policy import CompressionPolicy

__all__ = [
    "CacheConfig",
    "GEARLayerCache",
    "FP16LayerCache",
    "WindowLayerCache",
    "init_layer_cache",
    "prefill_layer_cache",
    "streaming_supported",
    "streaming_prefill_pipeline",
    "streaming_prefill_layer_cache",
    "append_token",
    "attend",
    "dense_kv",
    "extract_prefix_chunks",
    "splice_prefix_chunks",
    "NumericFault",
    "tree_finite",
    "splice_slot",
    "reset_slot",
    "prefill_into_slot",
    "fresh_batch1_cache",
    "PagedGEARLayerCache",
    "paged_supported",
    "PageLayout",
    "page_layout",
    "chunk_field_shapes",
    "page_field_shapes",
    "page_nbytes",
    "chunks_to_pages",
    "pages_to_chunks",
    "layer_pool",
    "unfold_rows",
    "unpack_words",
    "gather_pages",
    "init_paged_layer_cache",
    "paged_to_dense",
    "gather_pool_chunks",
    "scatter_pool_chunks",
    "zero_pool_pages",
    "append_token_paged",
    "attend_paged",
]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Static geometry of one attention layer's cache."""

    batch: int
    kv_heads: int
    head_dim: int
    capacity: int            # max tokens (multiple of chunk)
    policy: CompressionPolicy
    kind: str = "gear"       # "gear" | "fp16" | "window"
    window: int = 0          # for kind == "window"

    def __post_init__(self):
        if self.kind == "gear" and self.capacity % self.chunk:
            raise ValueError(f"capacity {self.capacity} not a multiple of chunk {self.chunk}")

    @property
    def chunk(self) -> int:
        return self.policy.buffer_size

    @property
    def n_chunks(self) -> int:
        return self.capacity // self.chunk

    def k_scheme(self):
        return self.policy.scheme_for("k")

    def v_scheme(self):
        return self.policy.scheme_for("v")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "k_packed", "k_scale", "k_zero", "v_packed", "v_scale", "v_zero",
        "k_a", "k_b", "v_a", "v_b",
        "k_sp_val", "k_sp_idx", "v_sp_val", "v_sp_idx",
        "buf_k", "buf_v", "length",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class GEARLayerCache:
    k_packed: Any; k_scale: Any; k_zero: Any
    v_packed: Any; v_scale: Any; v_zero: Any
    k_a: Any; k_b: Any; v_a: Any; v_b: Any
    k_sp_val: Any; k_sp_idx: Any; v_sp_val: Any; v_sp_idx: Any
    buf_k: Any; buf_v: Any
    length: Any


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "length"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class FP16LayerCache:
    k: Any
    v: Any
    length: Any


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "pos", "length"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class WindowLayerCache:
    """Ring buffer of the most recent ``window`` tokens (fp16)."""
    k: Any
    v: Any
    pos: Any      # int32 [B, window] absolute position held per ring slot (-1 empty)
    length: Any


# ---------------------------------------------------------------------------
# Shape helpers


def _k_stat_rows(cfg: CacheConfig) -> tuple[int, int]:
    scheme, group = cfg.k_scheme()
    if scheme == "per_channel":
        g = cfg.chunk if group is None else group
        return cfg.n_chunks * (cfg.chunk // g), cfg.head_dim
    g = cfg.head_dim if group is None else group
    return cfg.capacity, cfg.head_dim // g


def _v_stat_rows(cfg: CacheConfig) -> tuple[int, int]:
    scheme, group = cfg.v_scheme()
    g = cfg.head_dim if group is None else group
    return cfg.capacity, cfg.head_dim // g


def _sparse_caps(cfg: CacheConfig) -> tuple[int, int]:
    from repro.core.outlier import outlier_count
    ks = outlier_count(cfg.chunk, cfg.policy.sparsity)       # K: along tokens in chunk
    kv = outlier_count(cfg.head_dim, cfg.policy.sparsity)    # V: along channels
    return ks, kv


def init_layer_cache(cfg: CacheConfig, dtype=jnp.bfloat16):
    B, H, Dh, S = cfg.batch, cfg.kv_heads, cfg.head_dim, cfg.capacity
    if cfg.kind == "fp16":
        return FP16LayerCache(
            k=jnp.zeros((B, H, S, Dh), dtype),
            v=jnp.zeros((B, H, S, Dh), dtype),
            length=jnp.zeros((B,), jnp.int32),
        )
    if cfg.kind == "window":
        W = cfg.window
        return WindowLayerCache(
            k=jnp.zeros((B, H, W, Dh), dtype),
            v=jnp.zeros((B, H, W, Dh), dtype),
            pos=jnp.full((B, W), -1, jnp.int32),
            length=jnp.zeros((B,), jnp.int32),
        )
    pol = cfg.policy
    per = 32 // pol.bits
    C = cfg.n_chunks
    r = pol.rank
    ks, kvo = _sparse_caps(cfg)
    krows, kcols = _k_stat_rows(cfg)
    vrows, vcols = _v_stat_rows(cfg)
    use_lr, use_sp = pol.use_lowrank, pol.use_sparse
    z = lambda *shape: jnp.zeros(shape, dtype)
    zi = lambda *shape: jnp.zeros(shape, jnp.int32)
    k_is_channel = cfg.k_scheme()[0] == "per_channel"
    return GEARLayerCache(
        k_packed=zi(B, H, S, Dh // per),
        k_scale=z(B, H, krows, kcols),
        k_zero=z(B, H, krows, kcols),
        v_packed=zi(B, H, S, Dh // per),
        v_scale=z(B, H, vrows, vcols),
        v_zero=z(B, H, vrows, vcols),
        k_a=z(B, H, S, r) if use_lr else None,
        k_b=z(B, H, C, Dh, r) if use_lr else None,
        v_a=z(B, H, S, r) if use_lr else None,
        v_b=z(B, H, C, Dh, r) if use_lr else None,
        k_sp_val=(z(B, H, C, Dh, 2 * ks) if k_is_channel else z(B, H, S, 2 * kvo)) if use_sp else None,
        k_sp_idx=(zi(B, H, C, Dh, 2 * ks) if k_is_channel else zi(B, H, S, 2 * kvo)) if use_sp else None,
        v_sp_val=z(B, H, S, 2 * kvo) if use_sp else None,
        v_sp_idx=zi(B, H, S, 2 * kvo) if use_sp else None,
        buf_k=z(B, H, pol.buffer_size, Dh),
        buf_v=z(B, H, pol.buffer_size, Dh),
        length=jnp.zeros((B,), jnp.int32),
    )


def _slot_rows_update(dst: jnp.ndarray, vals: jnp.ndarray, start: jnp.ndarray,
                      need: jnp.ndarray | None = None) -> jnp.ndarray:
    """Write ``vals`` [B, H, r, ...] into ``dst`` [B, H, R, ...] at per-slot
    row offset ``start`` [B] along axis 2.

    Slots with ``need[b]`` False (and slots whose rows would run past the end
    of ``dst``) are redirected out of bounds, which the scatter drops — the
    mechanism that lets one batched write serve slots at different phases.
    """
    B, r, R = dst.shape[0], vals.shape[2], dst.shape[2]
    rows = start.astype(jnp.int32)[:, None] + jnp.arange(r, dtype=jnp.int32)[None, :]
    if need is not None:
        rows = jnp.where(need[:, None], rows, R)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    # advanced indices at axes (0, 2) move to the front: update is [B, r, H, ...]
    return dst.at[bidx, :, rows].set(
        jnp.moveaxis(vals, 2, 1).astype(dst.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Compression of chunk batches


def _compress_chunks(cfg: CacheConfig, k: jnp.ndarray, v: jnp.ndarray,
                     rank: int, key: jax.Array, fused: str = "off"):
    """Compress ``k``/``v`` [B, H, C', nb, Dh] -> dict of per-chunk arrays.

    C' is the number of chunks being compressed in this event (prefill: many,
    decode: 1).  Low-rank factors are zero-padded to ``policy.rank`` columns.

    ``fused`` selects the quantize/pack/stats/outlier implementation:
    "off" — :func:`repro.core.gear.compress_matrix` (plain XLA);
    "auto" — the fused ``gear_compress`` Pallas kernel on TPU, its bit-exact
    jnp oracle elsewhere; "interpret" — force the kernel in interpret mode
    (CI kernel lane).  The power-iteration low-rank step always runs in XLA,
    on the kernel-emitted quantization residual of this event's chunks only.
    """
    if fused != "off":
        return _compress_chunks_fused(cfg, k, v, rank, key, fused)
    pol = cfg.policy
    out = {}
    for name, x, kind in (("k", k, "k"), ("v", v, "v")):
        cm = gear_lib.compress_matrix(x, pol, kind, rank=rank, key=key)
        out[f"{name}_packed"] = cm.qt.packed
        out[f"{name}_scale"] = cm.qt.scale.astype(jnp.bfloat16)
        out[f"{name}_zero"] = cm.qt.zero.astype(jnp.bfloat16)
        if pol.use_lowrank:
            a, b = cm.a, cm.b
            pad = pol.rank - rank
            if pad:
                a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
                b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
            out[f"{name}_a"], out[f"{name}_b"] = a, b
        if pol.use_sparse:
            out[f"{name}_sp_val"] = cm.sparse.values.astype(jnp.bfloat16)
            out[f"{name}_sp_idx"] = cm.sparse.indices.astype(jnp.int32)
    return out


def _compress_chunks_fused(cfg: CacheConfig, k: jnp.ndarray, v: jnp.ndarray,
                           rank: int, key: jax.Array, fused: str):
    """Fused-kernel twin of :func:`_compress_chunks` (same output layout)."""
    from repro.kernels import ops as kernel_ops  # lazy: kernels import us

    pol = cfg.policy
    force = fused == "interpret"
    out = {}
    for name, x, kind in (("k", k, "k"), ("v", v, "v")):
        scheme, group = pol.scheme_for(kind)
        B, H, C, nb, Dh = x.shape
        vec_len = nb if scheme == "per_channel" else Dh
        n_out = ol_lib.outlier_count(vec_len, pol.sparsity) if pol.use_sparse else 0
        packed, scale, zero, spv, spi, resid = kernel_ops.gear_compress_chunks(
            x.reshape(B * H * C, nb, Dh), bits=pol.bits, scheme=scheme,
            group=group, n_out=n_out, stat_dtype=pol.stat_dtype,
            force_kernel=force, interpret=force)
        lead = (B, H, C)
        out[f"{name}_packed"] = packed.reshape(lead + packed.shape[1:])
        # rounded as quant.quantize rounds them, so readers in this program
        # see the stored stats (see quant.round_to_dtype)
        for field, stat in (("scale", scale), ("zero", zero)):
            out[f"{name}_{field}"] = q_lib.round_to_dtype(
                stat.reshape(lead + stat.shape[1:]), jnp.bfloat16).astype(jnp.bfloat16)
        if pol.use_lowrank:
            a, b = lr_lib.power_iteration(resid.reshape(lead + (nb, Dh)), rank,
                                          pol.power_iters, key)
            a = a.astype(jnp.bfloat16)
            b = b.astype(jnp.bfloat16)
            pad = pol.rank - rank
            if pad:
                a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
                b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
            out[f"{name}_a"], out[f"{name}_b"] = a, b
        if pol.use_sparse:
            out[f"{name}_sp_val"] = spv.reshape(lead + spv.shape[1:]).astype(jnp.bfloat16)
            out[f"{name}_sp_idx"] = spi.reshape(lead + spi.shape[1:]).astype(jnp.int32)
    return out


def _flatten_stat(cfg: CacheConfig, stat: jnp.ndarray, kind: str) -> jnp.ndarray:
    """[B,H,C',rows_per_chunk,cols] -> [B,H,C'*rows_per_chunk,cols]."""
    B, H = stat.shape[0], stat.shape[1]
    return stat.reshape(B, H, -1, stat.shape[-1])


def _store_prefill_chunks(cfg: CacheConfig, upd: dict, comp: dict,
                          n_full: int, start_chunk: int = 0) -> dict:
    """Write one compression event's ``C' = n_full / n_b`` chunks into the
    cache arrays of ``upd`` starting at chunk ``start_chunk`` (token
    ``start_chunk * n_b``).  Shared by monolithic prefill (one batched
    event, offset 0), streaming prefill (per-chunk events stacked by the
    compression scan — same layout either way), and suffix prefill over a
    cached prefix (``start_chunk`` = chunks already spliced from the prefix
    cache)."""
    pol = cfg.policy
    B, H = upd["k_packed"].shape[:2]
    t0 = start_chunk * cfg.chunk
    z4 = (0, 0, t0, 0)
    upd["k_packed"] = jax.lax.dynamic_update_slice(
        upd["k_packed"], comp["k_packed"].reshape(B, H, n_full, -1), z4)
    upd["v_packed"] = jax.lax.dynamic_update_slice(
        upd["v_packed"], comp["v_packed"].reshape(B, H, n_full, -1), z4)
    for kv in ("k", "v"):
        stat_s = _flatten_stat(cfg, comp[f"{kv}_scale"], kv)
        stat_z = _flatten_stat(cfg, comp[f"{kv}_zero"], kv)
        rpc = stat_s.shape[2] // max(n_full // cfg.chunk, 1)
        zs = (0, 0, start_chunk * rpc, 0)
        upd[f"{kv}_scale"] = jax.lax.dynamic_update_slice(upd[f"{kv}_scale"], stat_s, zs)
        upd[f"{kv}_zero"] = jax.lax.dynamic_update_slice(upd[f"{kv}_zero"], stat_z, zs)
        if pol.use_lowrank:
            a = comp[f"{kv}_a"].reshape(B, H, n_full, pol.rank)
            upd[f"{kv}_a"] = jax.lax.dynamic_update_slice(upd[f"{kv}_a"], a, z4)
            upd[f"{kv}_b"] = jax.lax.dynamic_update_slice(
                upd[f"{kv}_b"], comp[f"{kv}_b"], (0, 0, start_chunk, 0, 0))
        if pol.use_sparse:
            sv, si = comp[f"{kv}_sp_val"], comp[f"{kv}_sp_idx"]
            if kv == "v" or cfg.k_scheme()[0] != "per_channel":
                sv = sv.reshape(B, H, n_full, sv.shape[-1])
                si = si.reshape(B, H, n_full, si.shape[-1])
                upd[f"{kv}_sp_val"] = jax.lax.dynamic_update_slice(upd[f"{kv}_sp_val"], sv, z4)
                upd[f"{kv}_sp_idx"] = jax.lax.dynamic_update_slice(upd[f"{kv}_sp_idx"], si, z4)
            else:
                upd[f"{kv}_sp_val"] = jax.lax.dynamic_update_slice(
                    upd[f"{kv}_sp_val"], sv, (0, 0, start_chunk, 0, 0))
                upd[f"{kv}_sp_idx"] = jax.lax.dynamic_update_slice(
                    upd[f"{kv}_sp_idx"], si, (0, 0, start_chunk, 0, 0))
    return upd


def prefill_layer_cache(cfg: CacheConfig, cache, k: jnp.ndarray, v: jnp.ndarray,
                        key: jax.Array | None = None):
    """Fill a fresh layer cache from prefill K/V [B, H, n, Dh]."""
    n = k.shape[2]
    B = k.shape[0]
    full_len = jnp.full((B,), n, jnp.int32)
    if cfg.kind == "fp16":
        return FP16LayerCache(
            k=jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, 0, 0, 0)),
            v=jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, 0, 0, 0)),
            length=full_len,
        )
    if cfg.kind == "window":
        W = cfg.window
        # keep the last W tokens
        take = min(n, W)
        ks = k[:, :, n - take:, :]
        vs = v[:, :, n - take:, :]
        pos_vals = jnp.arange(n - take, n, dtype=jnp.int32)
        slots = pos_vals % W
        knew = cache.k.at[:, :, slots, :].set(ks.astype(cache.k.dtype))
        vnew = cache.v.at[:, :, slots, :].set(vs.astype(cache.v.dtype))
        pos = cache.pos.at[:, slots].set(pos_vals[None, :])
        return WindowLayerCache(k=knew, v=vnew, pos=pos, length=full_len)

    if key is None:
        key = jax.random.PRNGKey(0)
    pol = cfg.policy
    nb = cfg.chunk
    n_full = (n // nb) * nb
    C_new = n_full // nb
    upd = {f.name: getattr(cache, f.name) for f in dataclasses.fields(GEARLayerCache)}
    if C_new > 0:
        B, H, _, Dh = k.shape
        # f32 compression inputs: numerically identical for bf16 K/V (exact
        # widening; every internal step is f32 already) but avoids lax.top_k
        # on bf16, which hits a ~20x slower sort path on CPU
        kc = k[:, :, :n_full, :].reshape(B, H, C_new, nb, Dh).astype(jnp.float32)
        vc = v[:, :, :n_full, :].reshape(B, H, C_new, nb, Dh).astype(jnp.float32)
        comp = _compress_chunks(cfg, kc, vc, pol.rank, key)
        upd = _store_prefill_chunks(cfg, upd, comp, n_full)
    rem = n - n_full
    if rem:
        upd["buf_k"] = jax.lax.dynamic_update_slice(
            upd["buf_k"], k[:, :, n_full:, :].astype(upd["buf_k"].dtype), (0, 0, 0, 0))
        upd["buf_v"] = jax.lax.dynamic_update_slice(
            upd["buf_v"], v[:, :, n_full:, :].astype(upd["buf_v"].dtype), (0, 0, 0, 0))
    upd["length"] = full_len
    return GEARLayerCache(**upd)


def _attend_segments(n_chunks: int, segments: int = 4) -> list[tuple[int, int]]:
    """Equal [lo, hi) chunk segments for the prefix-view attend scans."""
    segments = min(segments, n_chunks)
    bounds = [round(n_chunks * j / segments) for j in range(segments + 1)]
    return [(lo, hi) for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]


def chunk_prefix_view(cfg: CacheConfig, cache, n_chunks: int):
    """Static view of the first ``n_chunks`` chunks of a GEAR cache.

    The streaming attend scan runs in segments, each against the shortest
    chunk prefix covering its queries — recovering most of the causal
    triangle the monolithic score matrix pays in full.  Scores beyond each
    query's own ``n_comp`` mask are exact zeros after the softmax either
    way, so segmenting only changes float accumulation width, never the
    math.  Buffer/length leaves pass through untouched.
    """
    if n_chunks >= cfg.n_chunks:
        return cache
    S_pre = n_chunks * cfg.chunk
    pol = cfg.policy
    scheme, group = cfg.k_scheme()
    if scheme == "per_channel":
        g = cfg.chunk if group is None else group
        k_rows = n_chunks * (cfg.chunk // g)
    else:
        k_rows = S_pre
    d = dict(
        k_packed=cache.k_packed[:, :, :S_pre],
        v_packed=cache.v_packed[:, :, :S_pre],
        k_scale=cache.k_scale[:, :, :k_rows],
        k_zero=cache.k_zero[:, :, :k_rows],
        v_scale=cache.v_scale[:, :, :S_pre],
        v_zero=cache.v_zero[:, :, :S_pre],
    )
    if pol.use_lowrank:
        d.update(k_a=cache.k_a[:, :, :S_pre], v_a=cache.v_a[:, :, :S_pre],
                 k_b=cache.k_b[:, :, :n_chunks], v_b=cache.v_b[:, :, :n_chunks])
    if pol.use_sparse:
        per_channel = scheme == "per_channel"
        d.update(
            k_sp_val=cache.k_sp_val[:, :, :n_chunks if per_channel else S_pre],
            k_sp_idx=cache.k_sp_idx[:, :, :n_chunks if per_channel else S_pre],
            v_sp_val=cache.v_sp_val[:, :, :S_pre],
            v_sp_idx=cache.v_sp_idx[:, :, :S_pre],
        )
    return dataclasses.replace(cache, **d)


def _assemble_scanned_chunks(cfg: CacheConfig, upd: dict, comp_s: dict,
                             n_full: int, start_chunk: int = 0) -> dict:
    """Stack a compression scan's per-chunk outputs (leaves [C', B, H, 1,
    ...]) into the batched-event layout and store them from chunk
    ``start_chunk`` (token 0 for a cold prefill)."""
    B, H = upd["k_packed"].shape[:2]

    def stack(t):
        C = t.shape[0]
        return jnp.moveaxis(t, 0, 2).reshape((B, H, C) + t.shape[4:])

    return _store_prefill_chunks(cfg, upd, {kk: stack(t) for kk, t in comp_s.items()},
                                 n_full, start_chunk)


def streaming_supported(cfg: CacheConfig) -> bool:
    """True when this layer cache can take the streaming prefill pipeline.

    The history scorer (``gear_decode`` / its oracles) streams one K-stat
    row per chunk, so — exactly like the fused decode path
    (:func:`repro.kernels.ops.fused_supported`) — it needs a GEAR cache
    with per-channel K quantization at chunk granularity.  Static; callers
    fall back to monolithic prefill when False.
    """
    if cfg.kind != "gear" or cfg.policy.is_fp16:
        return False
    scheme, group = cfg.k_scheme()
    if scheme != "per_channel":
        return False
    return (cfg.chunk if group is None else group) == cfg.chunk


def streaming_prefill_pipeline(cfg: CacheConfig, cache, n: int, chunk_xs,
                               tail_x, project, scale: float,
                               key: jax.Array | None = None,
                               fused: str = "auto", start_chunk: int = 0,
                               tail_is_padded: bool = False, true_n=None):
    """Shared driver of the streaming chunked prefill (compress-as-you-go).

    ``chunk_xs`` is a pytree of per-chunk inputs with a leading ``[C']``
    axis and ``tail_x`` the leftover-token inputs (or None);
    ``project(x) -> (q [B, Hq, T, Dh], k, v [B, H, T, Dh])`` maps either to
    the chunk's attention inputs — the model layer passes the raw residual-
    stream chunk and projects Q/K/V *inside the scans*, so the full-sequence
    FP16 K/V never exists.  Two carry-free ``lax.scan`` passes (loop fission
    of the compress-as-you-go loop — same dataflow, no per-step cache-carry
    copies):

    1. **Compression scan** — each chunk runs its compression event
       (:func:`_compress_chunks`, optionally through the fused
       ``gear_compress`` kernel); the stacked outputs are stored into the
       packed arrays in one shot (identical layout to the monolithic
       batched event).
    2. **Attend scan** — each chunk's queries attend the compressed history
       *before* their own chunk (scores masked at ``c · n_b``, factored
       ``gear_decode`` machinery) plus the in-flight FP16 chunk via a
       two-piece online softmax (:func:`repro.kernels.ops.gear_attend_block`),
       in segments over static chunk-prefix views.  Masking makes this
       bitwise identical to interleaving the two scans.

    Leftover tokens attend the same way (against the prefix view of the
    populated chunks only) and land in the FP16 streaming buffer.  Returns
    (cache, attn_out [B, Hq, n, Dh]).

    ``start_chunk`` > 0 runs the same pipeline as a **suffix** over a cache
    whose first ``start_chunk`` chunks are already populated (spliced from
    the prefix cache): new chunks are stored from chunk ``start_chunk``,
    every attend sees the cached chunks as compressed history (the global
    extent masks make each suffix chunk's output bit-identical to the cold
    run that computed those chunks itself), and the final length covers
    prefix + suffix.  ``n`` stays the *suffix* token count.

    ``tail_is_padded`` is the length-bucketing hook (mixed-length serving):
    ``n`` must then be a chunk multiple and the LAST ``n_b`` block of the
    inputs is a right-padded tail — ``true_n`` (traced, ``<= n``) real
    tokens overall, pad garbage after.  The tail block is kept OUT of the
    compression scan (no garbage chunk is ever closed or admitted to the
    prefix cache) and lands in the FP16 streaming buffer instead; causal
    masking keeps pad keys out of every real query's scores, and decode
    masks buffer rows at ``length`` — which is set from ``true_n`` — so
    the pad rows stay exact zeros forever after.
    """
    if not streaming_supported(cfg):
        raise ValueError(
            "streaming prefill requires a GEAR cache with per-channel K "
            f"stats at chunk granularity (got kind={cfg.kind!r}, "
            f"k_scheme={cfg.k_scheme()!r}, chunk={cfg.chunk})")
    from repro.kernels import ops as kernel_ops  # lazy: kernels import us

    if key is None:
        key = jax.random.PRNGKey(0)
    pol = cfg.policy
    nb = cfg.chunk
    if tail_is_padded and n % nb:
        raise ValueError(f"padded-tail prefill needs n % n_b == 0 (n={n}, "
                         f"n_b={nb})")
    C_new = n // nb - 1 if tail_is_padded else n // nb
    n_full = C_new * nb
    rem = n - n_full
    # A padded tail holds >= 1 real token, so the tightest static bound on
    # the true length is n - nb + 1; the engine re-checks the exact raw
    # length host-side at admission.
    n_min = n - nb + 1 if tail_is_padded else n
    if start_chunk * nb + n_min > cfg.capacity:
        raise ValueError(
            f"suffix prefill past capacity: start_chunk {start_chunk} * "
            f"{nb} + {n} tokens > capacity {cfg.capacity}")
    force = fused == "interpret"
    oracle = fused == "off"          # pin the jnp oracles even on TPU
    B = cache.length.shape[0]
    Dh = cfg.head_dim

    outs = []
    if C_new:
        def body_compress(_, x_c):
            _, k_c, v_c = project(x_c)
            comp = _compress_chunks(
                cfg, k_c[:, :, None].astype(jnp.float32),
                v_c[:, :, None].astype(jnp.float32), pol.rank, key, fused=fused)
            return None, comp

        with jax.named_scope("cache_update"):
            _, comp_s = jax.lax.scan(body_compress, None, chunk_xs)
            upd = {f.name: getattr(cache, f.name)
                   for f in dataclasses.fields(GEARLayerCache)}
            cache = GEARLayerCache(**_assemble_scanned_chunks(
                cfg, upd, comp_s, n_full, start_chunk))

        out_parts = []
        # Segment over the GLOBAL chunk range, then clip to the suffix: a
        # suffix chunk attends through exactly the prefix-view width the
        # cold run's schedule gave it, so the score shapes — and therefore
        # the float bits XLA's width-dependent reductions produce — match
        # the cold run, not just the masked math (start_chunk == 0 reduces
        # to plain segmentation of C_new).
        for g_lo, g_hi in _attend_segments(start_chunk + C_new):
            lo = max(g_lo - start_chunk, 0)
            hi = g_hi - start_chunk
            if hi <= lo:
                continue               # segment fully inside the cached prefix
            view = chunk_prefix_view(cfg, cache, g_hi)

            def body_attend(_, xs, view=view):
                c, x_c = xs
                q_c, k_c, v_c = project(x_c)
                out_c = kernel_ops.gear_attend_block(
                    cfg, view, q_c, k_c, v_c, (start_chunk + c) * nb, nb,
                    scale, force_kernel=force, interpret=force,
                    force_oracle=oracle)
                return None, out_c

            seg_xs = jax.tree.map(lambda t: t[lo:hi], chunk_xs)
            _, o = jax.lax.scan(
                body_attend, None,
                (jnp.arange(lo, hi, dtype=jnp.int32), seg_xs))
            out_parts.append(o)
        outs_s = jnp.concatenate(out_parts, axis=0)
        Hq = outs_s.shape[2]
        outs.append(jnp.moveaxis(outs_s, 0, 2).reshape(B, Hq, n_full, Dh))
    if rem:
        q_t, k_t, v_t = project(tail_x)
        view = chunk_prefix_view(cfg, cache, max(start_chunk + C_new, 1))
        out_t = kernel_ops.gear_attend_block(
            cfg, view, q_t, k_t, v_t, start_chunk * nb + n_full, rem, scale,
            force_kernel=force, interpret=force, force_oracle=oracle)
        z4 = (0, 0, 0, 0)
        cache = dataclasses.replace(
            cache,
            buf_k=jax.lax.dynamic_update_slice(
                cache.buf_k, k_t.astype(cache.buf_k.dtype), z4),
            buf_v=jax.lax.dynamic_update_slice(
                cache.buf_v, v_t.astype(cache.buf_v.dtype), z4))
        outs.append(out_t)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    n_real = n if true_n is None else true_n
    cache = dataclasses.replace(
        cache,
        length=jnp.full((B,), jnp.asarray(start_chunk * nb + n_real,
                                          jnp.int32)))
    return cache, out


def streaming_prefill_layer_cache(cfg: CacheConfig, cache, q: jnp.ndarray,
                                  k: jnp.ndarray, v: jnp.ndarray,
                                  scale: float, key: jax.Array | None = None,
                                  fused: str = "auto", start_chunk: int = 0,
                                  tail_is_padded: bool = False, true_n=None):
    """Streaming chunked prefill over precomputed q/k/v (reference entry).

    q: [B, Hq, n, Dh]; k, v: [B, H, n, Dh] — sliced per chunk into
    :func:`streaming_prefill_pipeline` (the model layer instead projects
    per chunk inside the scans; see
    :func:`repro.models.attention.attention_prefill_streaming`).

    Chunk compression is bit-identical to :func:`prefill_layer_cache`'s
    batched event (batch-invariant keys + per-chunk-independent math), so
    the resulting cache is bit-identical to a monolithic prefill of the
    same tokens; only the attention output differs (history is attended in
    compressed form — the same semantics decode already has).

    Returns (cache, attn_out [B, Hq, n, Dh] in q's dtype).
    ``fused``: "auto"/"off" (kernels on TPU, jnp oracles elsewhere) or
    "interpret" (force the Pallas kernels in interpret mode).
    ``start_chunk`` > 0 treats q/k/v as the *suffix* after that many
    already-populated chunks of ``cache`` (the prefix-cache splice path).
    ``tail_is_padded`` / ``true_n`` take the bucketed mixed-length path
    (see :func:`streaming_prefill_pipeline`).
    """
    pol_nb = cfg.chunk
    B, Hq, n, Dh = q.shape
    H = cfg.kv_heads
    C_new = n // pol_nb - 1 if tail_is_padded else n // pol_nb
    n_full = C_new * pol_nb

    def stack(x, heads):
        return jnp.moveaxis(
            x[:, :, :n_full].reshape(B, heads, C_new, pol_nb, Dh), 2, 0)

    chunk_xs = (stack(q, Hq), stack(k, H), stack(v, H)) if C_new else None
    tail_x = ((q[:, :, n_full:], k[:, :, n_full:], v[:, :, n_full:])
              if n > n_full else None)
    return streaming_prefill_pipeline(cfg, cache, n, chunk_xs, tail_x,
                                      lambda x: x, scale, key, fused,
                                      start_chunk, tail_is_padded, true_n)


def append_token(cfg: CacheConfig, cache, k_t: jnp.ndarray, v_t: jnp.ndarray,
                 key: jax.Array | None = None):
    """Append one token's K/V [B, H, Dh] per slot; compress full buffers.

    Each slot advances at its own ``length[b]``: writes land at per-slot
    offsets, and a slot whose streaming buffer just filled gets its chunk
    compressed and scattered into packed storage (slots not at a chunk
    boundary drop their writes).  Past capacity the packed / fp16 / window
    writes drop, but the GEAR streaming buffer keeps ring-wrapping, so a
    live request must never outgrow capacity (the scheduler rejects it at
    submit time); an *idle* slot may keep riding the batched step with
    garbage state until it is respliced, since a splice rewrites the row.
    """
    if cfg.kind == "fp16":
        knew = _slot_rows_update(cache.k, k_t[:, :, None, :], cache.length)
        vnew = _slot_rows_update(cache.v, v_t[:, :, None, :], cache.length)
        return FP16LayerCache(k=knew, v=vnew, length=cache.length + 1)
    if cfg.kind == "window":
        W = cfg.window
        slot = cache.length % W
        knew = _slot_rows_update(cache.k, k_t[:, :, None, :], slot)
        vnew = _slot_rows_update(cache.v, v_t[:, :, None, :], slot)
        B = cache.pos.shape[0]
        pos = cache.pos.at[jnp.arange(B), slot].set(cache.length)
        return WindowLayerCache(k=knew, v=vnew, pos=pos, length=cache.length + 1)

    pol = cfg.policy
    nb = cfg.chunk
    if key is None:
        key = jax.random.PRNGKey(0)
    buf_pos = cache.length % nb
    buf_k = _slot_rows_update(cache.buf_k, k_t[:, :, None, :], buf_pos)
    buf_v = _slot_rows_update(cache.buf_v, v_t[:, :, None, :], buf_pos)
    cache = dataclasses.replace(cache, buf_k=buf_k, buf_v=buf_v, length=cache.length + 1)

    def compress(c):
        # Per-slot chunk of the buffer just filled; slots not at a boundary
        # compute a throwaway compression whose writes are dropped.
        need = (c.length % nb == 0) & (c.length > 0) & (c.length <= cfg.capacity)
        cidx = jnp.maximum(c.length - 1, 0) // nb
        B, H, _, Dh = c.buf_k.shape
        kc = c.buf_k[:, :, None, :, :].astype(jnp.float32)  # [B,H,1,nb,Dh]
        vc = c.buf_v[:, :, None, :, :].astype(jnp.float32)
        # NOTE: the compression key is slot- and step-invariant so that a
        # request spliced into a live batch reproduces its solo compression
        # bit-for-bit (see DESIGN.md §splice isolation).
        comp = _compress_chunks(cfg, kc, vc, pol.rank_decode, key)
        upd = {f.name: getattr(c, f.name) for f in dataclasses.fields(GEARLayerCache)}
        tok0 = cidx * nb
        upd["k_packed"] = _slot_rows_update(
            upd["k_packed"], comp["k_packed"].reshape(B, H, nb, -1), tok0, need)
        upd["v_packed"] = _slot_rows_update(
            upd["v_packed"], comp["v_packed"].reshape(B, H, nb, -1), tok0, need)
        for kv in ("k", "v"):
            stat_s = _flatten_stat(cfg, comp[f"{kv}_scale"], kv)
            stat_z = _flatten_stat(cfg, comp[f"{kv}_zero"], kv)
            rows_per_chunk = stat_s.shape[2]
            upd[f"{kv}_scale"] = _slot_rows_update(
                upd[f"{kv}_scale"], stat_s, cidx * rows_per_chunk, need)
            upd[f"{kv}_zero"] = _slot_rows_update(
                upd[f"{kv}_zero"], stat_z, cidx * rows_per_chunk, need)
            if pol.use_lowrank:
                a = comp[f"{kv}_a"].reshape(B, H, nb, pol.rank)
                upd[f"{kv}_a"] = _slot_rows_update(upd[f"{kv}_a"], a, tok0, need)
                upd[f"{kv}_b"] = _slot_rows_update(
                    upd[f"{kv}_b"], comp[f"{kv}_b"], cidx, need)
            if pol.use_sparse:
                sv, si = comp[f"{kv}_sp_val"], comp[f"{kv}_sp_idx"]
                if kv == "v" or cfg.k_scheme()[0] != "per_channel":
                    sv = sv.reshape(B, H, nb, sv.shape[-1])
                    si = si.reshape(B, H, nb, si.shape[-1])
                    upd[f"{kv}_sp_val"] = _slot_rows_update(upd[f"{kv}_sp_val"], sv, tok0, need)
                    upd[f"{kv}_sp_idx"] = _slot_rows_update(upd[f"{kv}_sp_idx"], si, tok0, need)
                else:
                    upd[f"{kv}_sp_val"] = _slot_rows_update(upd[f"{kv}_sp_val"], sv, cidx, need)
                    upd[f"{kv}_sp_idx"] = _slot_rows_update(upd[f"{kv}_sp_idx"], si, cidx, need)
        return GEARLayerCache(**upd)

    any_boundary = jnp.any((cache.length % nb == 0) & (cache.length > 0)
                           & (cache.length <= cfg.capacity))
    return jax.lax.cond(any_boundary, compress, lambda c: c, cache)


# ---------------------------------------------------------------------------
# Attention over the compressed cache


def _expand_stat(cfg: CacheConfig, stat: jnp.ndarray, kind: str) -> jnp.ndarray:
    """Expand compact scale/zero rows back to [B, H, S, Dh]."""
    scheme, group = cfg.k_scheme() if kind == "k" else cfg.v_scheme()
    B, H = stat.shape[0], stat.shape[1]
    S, Dh = cfg.capacity, cfg.head_dim
    if scheme == "per_channel":
        g = cfg.chunk if group is None else group
        x = jnp.repeat(stat[:, :, :, None, :], g, axis=3)
        return x.reshape(B, H, S, Dh)
    g = Dh if group is None else group
    x = jnp.repeat(stat[:, :, :, :, None], g, axis=4)
    return x.reshape(B, H, S, Dh)


def _dequant_backbone(cfg: CacheConfig, packed, scale, zero, kind: str,
                      dtype=jnp.float32) -> jnp.ndarray:
    codes = packing.unpack(packed, cfg.policy.bits, cfg.head_dim).astype(dtype)
    s = _expand_stat(cfg, scale.astype(dtype), kind)
    z = _expand_stat(cfg, zero.astype(dtype), kind)
    return codes * s + z


def _sparse_dense(cfg: CacheConfig, sp_val, sp_idx, kind: str) -> jnp.ndarray:
    """Densify cached outliers to [B, H, S, Dh] (jnp path only)."""
    B, H = sp_val.shape[0], sp_val.shape[1]
    S, Dh, nb, C = cfg.capacity, cfg.head_dim, cfg.chunk, cfg.n_chunks
    per_channel = kind == "k" and cfg.k_scheme()[0] == "per_channel"
    if per_channel:
        # sp_* [B,H,C,Dh,2k]: token index within chunk
        kk = sp_val.shape[-1]
        onehot = sp_idx[..., None] == jnp.arange(nb)  # [B,H,C,Dh,2k,nb]
        dense = jnp.einsum("bhcdk,bhcdkn->bhcnd", sp_val.astype(jnp.float32),
                           onehot.astype(jnp.float32))
        return dense.reshape(B, H, S, Dh)
    # sp_* [B,H,S,2k]: channel index within Dh
    onehot = sp_idx[..., None] == jnp.arange(Dh)  # [B,H,S,2k,Dh]
    return jnp.einsum("bhsk,bhskd->bhsd", sp_val.astype(jnp.float32),
                      onehot.astype(jnp.float32))


def _lowrank_dense(cfg: CacheConfig, a, b) -> jnp.ndarray:
    """Materialize per-chunk A·Bᵀ to [B, H, S, Dh] (test/debug path)."""
    B, H = a.shape[0], a.shape[1]
    C, nb, Dh, r = cfg.n_chunks, cfg.chunk, cfg.head_dim, cfg.policy.rank
    ac = a.reshape(B, H, C, nb, r).astype(jnp.float32)
    return jnp.einsum("bhcnr,bhcdr->bhcnd", ac, b.astype(jnp.float32)).reshape(B, H, S := cfg.capacity, Dh)


def dense_kv(cfg: CacheConfig, cache) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reconstruct dense K̂/V̂ [B, H, S(+buffer), Dh] — reference/debug path.

    Buffer tokens are appended in fp16, so positions < length round-trip.
    """
    if cfg.kind == "fp16":
        return cache.k.astype(jnp.float32), cache.v.astype(jnp.float32)
    if cfg.kind == "window":
        return cache.k.astype(jnp.float32), cache.v.astype(jnp.float32)
    pol = cfg.policy
    k_hat = _dequant_backbone(cfg, cache.k_packed, cache.k_scale, cache.k_zero, "k")
    v_hat = _dequant_backbone(cfg, cache.v_packed, cache.v_scale, cache.v_zero, "v")
    if pol.use_lowrank:
        k_hat = k_hat + _lowrank_dense(cfg, cache.k_a, cache.k_b)
        v_hat = v_hat + _lowrank_dense(cfg, cache.v_a, cache.v_b)
    if pol.use_sparse:
        k_hat = k_hat + _sparse_dense(cfg, cache.k_sp_val, cache.k_sp_idx, "k")
        v_hat = v_hat + _sparse_dense(cfg, cache.v_sp_val, cache.v_sp_idx, "v")
    # overlay buffered (uncompressed) tokens — per-slot buffer windows
    nb = cfg.chunk
    n_comp = (cache.length // nb) * nb                       # [B]
    tok = jnp.arange(cfg.capacity)
    buf_slot = tok[None, :] - n_comp[:, None]                # [B, S]
    in_buf = (buf_slot >= 0) & (buf_slot < nb) & (tok[None, :] < cache.length[:, None])
    bslot = jnp.clip(buf_slot, 0, nb - 1)
    k_buf = jnp.take_along_axis(cache.buf_k.astype(jnp.float32),
                                bslot[:, None, :, None], axis=2)
    v_buf = jnp.take_along_axis(cache.buf_v.astype(jnp.float32),
                                bslot[:, None, :, None], axis=2)
    mask = in_buf[:, None, :, None]
    k_hat = jnp.where(mask, k_buf, k_hat)
    v_hat = jnp.where(mask, v_buf, v_hat)
    valid = (tok[None, :] < cache.length[:, None])[:, None, :, None]
    return k_hat * valid, v_hat * valid


def attend(cfg: CacheConfig, cache, q: jnp.ndarray, scale: float,
           use_factored: bool = True) -> jnp.ndarray:
    """Decode attention of one query token over the cache.

    q: [B, Hq, Dh] with Hq = G * kv_heads (GQA).  Returns [B, Hq, Dh].
    ``use_factored`` selects the factored low-rank/sparse score path (the
    paper's separate forward path); False falls back to dense reconstruction.
    """
    B, Hq, Dh = q.shape
    H = cfg.kv_heads
    G = Hq // H
    qf = q.astype(jnp.float32).reshape(B, H, G, Dh)

    if cfg.kind == "window":
        kf, vf = cache.k.astype(jnp.float32), cache.v.astype(jnp.float32)
        scores = jnp.einsum("bhgd,bhwd->bhgw", qf, kf) * scale
        valid = (cache.pos >= 0) & (cache.pos < cache.length[:, None])  # [B, W]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgw,bhwd->bhgd", w, vf)
        return out.reshape(B, Hq, Dh).astype(q.dtype)

    if cfg.kind == "fp16" or not use_factored:
        kf, vf = dense_kv(cfg, cache)
        scores = jnp.einsum("bhgd,bhsd->bhgs", qf, kf) * scale
        valid = jnp.arange(cfg.capacity)[None, :] < cache.length[:, None]  # [B, S]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgs,bhsd->bhgd", w, vf)
        return out.reshape(B, Hq, Dh).astype(q.dtype)

    pol = cfg.policy
    nb, C, S = cfg.chunk, cfg.n_chunks, cfg.capacity
    n_comp = (cache.length // nb) * nb        # [B] per-slot compressed extent
    n_buf = cache.length - n_comp             # [B] per-slot buffer fill
    cdt = jnp.bfloat16  # dequant/compute dtype; accumulations stay f32
    f32 = jnp.float32
    qc = qf.astype(cdt)

    # --- scores over the compressed region -------------------------------
    k_codes = packing.unpack(cache.k_packed, pol.bits, Dh).astype(cdt)
    if cfg.k_scheme()[0] == "per_channel":
        g = cfg.chunk if cfg.k_scheme()[1] is None else cfg.k_scheme()[1]
        rows = S // g
        sc = cache.k_scale.astype(cdt).reshape(B, H, rows, Dh)
        zr = cache.k_zero.astype(cdt).reshape(B, H, rows, Dh)
        # scores = (q ⊙ scale_row)·codes + q·zero_row  per row-group of g tokens
        q_sc = jnp.einsum("bhgd,bhrd->bhgrd", qc, sc)
        codes_r = k_codes.reshape(B, H, rows, g, Dh)
        s_bb = jnp.einsum("bhgrd,bhrnd->bhgrn", q_sc, codes_r,
                          preferred_element_type=cdt)
        s_bb = s_bb + jnp.einsum("bhgd,bhrd->bhgr", qc, zr,
                                 preferred_element_type=cdt)[..., None]
        s_bb = s_bb.reshape(B, H, G, S)
    else:
        k_hat = _dequant_backbone(cfg, cache.k_packed, cache.k_scale,
                                  cache.k_zero, "k", dtype=cdt)
        s_bb = jnp.einsum("bhgd,bhsd->bhgs", qc, k_hat, preferred_element_type=cdt)

    if pol.use_lowrank:
        # factored path: (q·B_c)·A_cᵀ per chunk
        qb = jnp.einsum("bhgd,bhcdr->bhgcr", qc, cache.k_b.astype(cdt))
        a_c = cache.k_a.astype(cdt).reshape(B, H, C, nb, pol.rank)
        s_lr = jnp.einsum("bhgcr,bhcnr->bhgcn", qb, a_c,
                          preferred_element_type=cdt).reshape(B, H, G, S)
        s_bb = s_bb + s_lr
    if pol.use_sparse:
        if cfg.k_scheme()[0] == "per_channel":
            # Densify K outliers with a vals-only scatter (index tensor has
            # no G or Dh-column blowup), then one q·sp_dense dot — §Perf
            # iterations 3+5.
            K2 = cache.k_sp_val.shape[-1]
            rows_k = B * H * C * Dh
            sp_cdn = jnp.zeros((rows_k, nb), cdt).at[
                jnp.arange(rows_k, dtype=jnp.int32)[:, None],
                cache.k_sp_idx.reshape(rows_k, K2)].add(
                cache.k_sp_val.astype(cdt).reshape(rows_k, K2))
            sp_cdn = sp_cdn.reshape(B, H, C, Dh, nb)
            s_sp = jnp.einsum("bhgd,bhcdn->bhgcn", qc, sp_cdn,
                              preferred_element_type=cdt)
            s_bb = s_bb + s_sp.reshape(B, H, G, S)
        else:
            sp_dense = _sparse_dense(cfg, cache.k_sp_val, cache.k_sp_idx, "k")
            s_bb = s_bb + jnp.einsum("bhgd,bhsd->bhgs", qf, sp_dense)

    # --- buffer scores -----------------------------------------------------
    s_buf = jnp.einsum("bhgd,bhnd->bhgn", qc, cache.buf_k.astype(cdt),
                       preferred_element_type=cdt)

    # --- masks + two-piece online softmax (no concat copy; §Perf iter 5) ----
    neg = jnp.asarray(-1e30, s_bb.dtype)
    m_bb = (jnp.arange(S)[None, :] < n_comp[:, None])[:, None, None, :]
    m_buf = (jnp.arange(nb)[None, :] < n_buf[:, None])[:, None, None, :]
    s_bb = jnp.where(m_bb, s_bb * scale, neg)
    s_buf = jnp.where(m_buf, s_buf * scale, neg)
    m_all = jnp.maximum(jnp.max(s_bb, axis=-1), jnp.max(s_buf, axis=-1))[..., None]
    e_bb = jnp.exp((s_bb - m_all).astype(f32))
    e_buf = jnp.exp((s_buf - m_all).astype(f32))
    denom = jnp.sum(e_bb, axis=-1, keepdims=True) + jnp.sum(e_buf, axis=-1, keepdims=True)
    w_c = e_bb / denom
    w_buf = e_buf / denom

    # --- weighted values -----------------------------------------------------
    w_cb = w_c.astype(cdt)
    v_codes = packing.unpack(cache.v_packed, pol.bits, Dh).astype(cdt)
    v_sc = _expand_stat(cfg, cache.v_scale.astype(cdt), "v")
    v_zr = _expand_stat(cfg, cache.v_zero.astype(cdt), "v")
    v_hat = v_codes * v_sc + v_zr
    if pol.use_sparse:
        # densify V outliers with a vals-only scatter (no per-G duplication)
        # and fold into the backbone dequant — the add fuses into the dot's
        # operand, so the only extra traffic is the tiny update set
        # (§Perf iteration 4).
        K2v = cache.v_sp_val.shape[-1]
        rows_v = B * H * S
        sp_dense_v = jnp.zeros((rows_v, Dh), cdt).at[
            jnp.arange(rows_v, dtype=jnp.int32)[:, None],
            cache.v_sp_idx.reshape(rows_v, K2v)].add(
            cache.v_sp_val.astype(cdt).reshape(rows_v, K2v))
        v_hat = v_hat + sp_dense_v.reshape(B, H, S, Dh)
    out = jnp.einsum("bhgs,bhsd->bhgd", w_cb, v_hat,
                     preferred_element_type=f32)
    if pol.use_lowrank:
        # factored: (w·A_c)·B_cᵀ per chunk
        w_chunk = w_cb.reshape(B, H, G, C, nb)
        wa = jnp.einsum("bhgcn,bhcnr->bhgcr", w_chunk,
                        cache.v_a.astype(cdt).reshape(B, H, C, nb, pol.rank))
        out = out + jnp.einsum("bhgcr,bhcdr->bhgd", wa, cache.v_b.astype(cdt),
                               preferred_element_type=f32)
    out = out + jnp.einsum("bhgn,bhnd->bhgd", w_buf.astype(cdt),
                           cache.buf_v.astype(cdt), preferred_element_type=f32)
    return out.reshape(B, Hq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Prefix-chunk extraction / splicing (cross-request prefix cache)


def _chunk_row_axes(cfg: CacheConfig) -> dict[str, tuple[int, int]]:
    """Chunk-indexed row layout of every GEAR cache array.

    Maps field name -> ``(rows_per_chunk, row_axis_from_end)``: chunk ``c``
    of a cache array occupies rows ``[c * rows_per_chunk, (c+1) *
    rows_per_chunk)`` along the given axis (counted from the end, so the
    same spec serves plain ``[B, H, ...]`` layer caches and the engine's
    repeat-stacked ``[R, B, H, ...]`` leaves).  Buffer / length leaves are
    deliberately absent: they are per-slot streaming state, never part of a
    chunk.
    """
    if cfg.kind != "gear":
        raise ValueError(f"prefix chunks require a GEAR cache, got {cfg.kind!r}")
    pol = cfg.policy
    nb = cfg.chunk
    C = cfg.n_chunks
    spec: dict[str, tuple[int, int]] = {
        "k_packed": (nb, -2), "v_packed": (nb, -2),
        "k_scale": (_k_stat_rows(cfg)[0] // C, -2),
        "k_zero": (_k_stat_rows(cfg)[0] // C, -2),
        "v_scale": (_v_stat_rows(cfg)[0] // C, -2),
        "v_zero": (_v_stat_rows(cfg)[0] // C, -2),
    }
    if pol.use_lowrank:
        spec.update(k_a=(nb, -2), v_a=(nb, -2), k_b=(1, -3), v_b=(1, -3))
    if pol.use_sparse:
        k_chan = cfg.k_scheme()[0] == "per_channel"
        spec.update(k_sp_val=(1, -3) if k_chan else (nb, -2),
                    k_sp_idx=(1, -3) if k_chan else (nb, -2),
                    v_sp_val=(nb, -2), v_sp_idx=(nb, -2))
    return spec


def extract_prefix_chunks(cfg: CacheConfig, cache, n_chunks: int,
                          start_chunk: int = 0) -> list[dict]:
    """Slice chunks ``[start_chunk, start_chunk + n_chunks)`` of a GEAR
    layer cache into independent per-chunk payload dicts.

    Works on a plain ``[B, H, ...]`` layer cache or on one position of the
    engine's repeat-stacked tree (leaves ``[R, B, H, ...]``): the chunk row
    axes are addressed from the end, so extra leading dims pass through.
    Each payload holds every compressed-array slice of one chunk (packed
    codes, quant stats, low-rank factors, outliers) — exactly the state
    :func:`splice_prefix_chunks` needs to reproduce the chunk in any slot
    of any cache with the same geometry.  Buffer and length are not
    extracted (a cached prefix is always chunk-aligned).
    """
    spec = _chunk_row_axes(cfg)
    out = []
    for c in range(start_chunk, start_chunk + n_chunks):
        payload = {}
        for field, (rpc, ax) in spec.items():
            arr = getattr(cache, field)
            idx = [slice(None)] * arr.ndim
            idx[arr.ndim + ax] = slice(c * rpc, (c + 1) * rpc)
            payload[field] = arr[tuple(idx)]
        out.append(payload)
    return out


def splice_prefix_chunks(cfg: CacheConfig, cache, slot, chunks: list[dict],
                         start_chunk: int = 0, batch_axis: int = 0):
    """Write per-chunk payloads (from :func:`extract_prefix_chunks`) into
    batch row ``slot`` of ``cache`` as chunks ``[start_chunk, start_chunk +
    len(chunks))``.

    The payloads are concatenated per field and written with one
    ``dynamic_update_slice`` each — the same batch-row write the slot-
    splice protocol uses.  ``batch_axis`` is 0 for a single layer cache and
    1 for the engine's repeat-stacked ``[R, B, ...]`` leaves.  ``length``
    is left untouched: the caller owns it (suffix prefill sets it to
    prefix + suffix).  Pass-through leaves the chunk spec does not cover
    (streaming buffer, length) alias ``cache``'s arrays, so the result
    must NOT be donated into a jitted program while ``cache`` (e.g. the
    engine's memoized empty scaffold) is still live.
    """
    if not chunks:
        return cache
    slot = jnp.asarray(slot, jnp.int32)
    spec = _chunk_row_axes(cfg)
    upd = {}
    for field, (rpc, ax) in spec.items():
        dst = getattr(cache, field)
        row_axis = dst.ndim + ax
        parts = [ch[field] for ch in chunks]
        seg = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=row_axis)
        starts = [jnp.asarray(0, jnp.int32)] * dst.ndim
        starts[batch_axis] = slot
        starts[row_axis] = jnp.asarray(start_chunk * rpc, jnp.int32)
        upd[field] = jax.lax.dynamic_update_slice(
            dst, seg.astype(dst.dtype), tuple(starts))
    return dataclasses.replace(cache, **upd)


class NumericFault(RuntimeError):
    """A compressed chunk failed the NaN/Inf finiteness guard.

    Raised at the two trust boundaries where a closed chunk becomes shared
    state: the engine's post-prefill guard (before the batch-1 cache is
    spliced into the live batched tree) and :meth:`ChunkStore.put` when the
    prefix cache validates payloads on insert.  Quarantine semantics: the
    poisoned request fails, its slot is reset and pages released, and no
    trie node is created — co-batched requests never see the bad values.
    """


def tree_finite(tree) -> jnp.ndarray:
    """Scalar bool: every float/complex leaf of ``tree`` is fully finite.

    Integer leaves (packed codes, sparse indices, lengths, page tables)
    are skipped — they cannot hold NaN/Inf and the guard stays one fused
    reduction over the few inexact leaves (quant stats, low-rank factors,
    outlier values, streaming buffer).  Safe under ``jax.jit``; returns
    True for a tree with no inexact leaves.
    """
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)]
    ok = jnp.asarray(True)
    for leaf in leaves:
        ok = jnp.logical_and(ok, jnp.isfinite(leaf).all())
    return ok


# ---------------------------------------------------------------------------
# Paged compressed KV pool (vLLM-style block tables over GEAR chunks)
#
# One **page** holds one n_b-token chunk's compressed fields for one layer,
# every KV head of it.  A per-slot **block table** ``[B, C]`` of page ids
# maps logical chunk ``c`` of slot ``b`` to its pool page; page 0 is the
# permanently-zero reserved page, so table entries past a slot's allocated
# extent read as the dense layout's zeros — which is what makes
# ``paged_to_dense`` *bitwise* equal to the dense-slot cache (the allocator
# zeroes fresh pages at admission to keep the invariant; see DESIGN.md §5).
# The streaming buffer and ``length`` stay per-slot: only closed (immutable)
# chunks live in the pool, which is why prefix-cache sharing is pure
# refcounting with no copy-on-write copies ever needed.
#
# **Page layout.**  A page is stored lane-dense, the way the TPU tiles
# memory: two leaves, ``codes`` (int32) and ``side`` (the cache dtype), each
# ``[rows, 128]`` a page with whole tiles of rows (8 rows of 32-bit data,
# 16 of 16-bit), so a leaf's default device layout is the unpadded
# row-major one the decode kernel DMAs page by page.  One rule places every
# pooled field, from its dense chunk shape (:func:`page_layout`):
#
# * codes are repacked token-major, ``[a, Dh]`` int32 words with ``a =
#   ceil(n_b / per)``: word ``(r, d)`` holds token ``r + a·j`` of channel
#   ``d`` in bits ``[j·bits, (j+1)·bits)``, so unpacking is whole-vreg
#   shifts and masks;
# * every other field is one head's dense chunk field as a 2-D form with
#   its longer axis on lanes (``k_b [Dh, r] -> [r, Dh]``, ``v_scale [n_b, 1]
#   -> [1, n_b]``); sparse indices are stored as exact values of the cache
#   dtype, in the ``side`` leaf beside their values;
# * a form ``[a, b]`` is padded to ``w`` lanes (a power of two up to 128,
#   else a multiple of 128) and folded into 128-lane rows: narrow forms cut
#   their rows into ``128 / w`` groups of ``ceil(a·w / 128)`` rows, group
#   ``s`` on lanes ``[s·w, (s+1)·w)``; wide forms cut their lanes into
#   ``w / 128`` groups, group ``t`` on rows ``[t·a, (t+1)·a)``;
# * a leaf holds its fields in ``_POOLED_FIELDS`` order, each field's heads
#   in order, and its rows are padded to whole tiles.
#
# Chunks change form only where they enter or leave the pool
# (:func:`chunks_to_pages`, :func:`pages_to_chunks`): the admission splice,
# the closing-chunk write of :func:`append_token_paged`, the prefix-hit
# gather, :func:`paged_to_dense` and the paged oracle.

LANES = 128


@dataclasses.dataclass(frozen=True)
class PageField:
    """Where one pooled field sits in a page.

    ``chunk_shape`` / ``chunk_dtype`` are one head's dense chunk field (its
    ``_chunk_row_axes`` rows); ``form`` is the page form ``[a, b]``,
    ``transposed`` when it swaps the dense field's two axes; ``width`` the
    lanes a form row is padded to.  Head ``h`` occupies rows ``[offset +
    h·rows, offset + (h+1)·rows)`` of ``leaf``.
    """
    name: str
    leaf: str
    offset: int
    rows: int
    form: tuple[int, int]
    width: int
    transposed: bool
    chunk_shape: tuple[int, ...]
    chunk_dtype: str


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """Page geometry of one layer.  Hashable: it rides ``jit`` static
    arguments into the paged kernel and its oracle."""
    heads: int
    chunk: int
    bits: int
    fields: tuple[PageField, ...]
    codes_rows: int
    side_rows: int
    side_dtype: str

    def field(self, name: str) -> PageField | None:
        return next((f for f in self.fields if f.name == name), None)

    def leaf_shapes(self) -> dict:
        """``{leaf: (page shape, dtype)}`` of the pool's two leaves."""
        return {"codes": ((self.codes_rows, LANES), jnp.dtype(jnp.int32)),
                "side": ((self.side_rows, LANES), jnp.dtype(self.side_dtype))}


def _lane_width(b: int) -> int:
    """Lanes a form row of ``b`` elements takes: a power of two up to 128
    (so narrow rows share a 128-lane row evenly), else a multiple of 128."""
    return 1 << (b - 1).bit_length() if b <= LANES else -(-b // LANES) * LANES


def _folded_rows(a: int, w: int) -> int:
    return -(-a * w // LANES) if w <= LANES else a * (w // LANES)


def chunk_field_shapes(cfg: CacheConfig, dtype=jnp.bfloat16) -> dict:
    """Per-field ``(shape, dtype)`` of one chunk of the dense layout, all
    heads: drop the batch axis, slice the chunk-row axis
    (``_chunk_row_axes``) to one chunk's rows.  E.g. ``k_packed [1, H, S,
    Lp] -> (H, n_b, Lp)``, ``k_b [1, H, C, Dh, r] -> (H, 1, Dh, r)``."""
    abs1 = jax.eval_shape(lambda: init_layer_cache(cfg, dtype))
    out = {}
    for field, (rpc, ax) in _chunk_row_axes(cfg).items():
        leaf = getattr(abs1, field)
        if leaf is None:
            continue
        shape = list(leaf.shape[1:])          # drop the batch axis
        shape[len(shape) + ax] = rpc          # row axis counted from the end
        out[field] = (tuple(shape), leaf.dtype)
    return out


@functools.lru_cache(maxsize=64)
def _page_layout(cfg1: CacheConfig, dtype_name: str) -> PageLayout:
    dtype = jnp.dtype(dtype_name)
    if max(cfg1.chunk, cfg1.head_dim) > 2 ** (jnp.finfo(dtype).nmant + 1):
        raise ValueError(
            f"sparse indices up to {max(cfg1.chunk, cfg1.head_dim)} are not "
            f"exact in {dtype.name}, the page's side dtype")
    per = 32 // cfg1.policy.bits
    fields, used = [], {"codes": 0, "side": 0}
    shapes = chunk_field_shapes(cfg1, dtype)
    for name in (n for n in _POOLED_FIELDS if n in shapes):
        (heads, *chunk_shape), cdt = shapes[name]
        if name.endswith("_packed"):
            leaf, transposed = "codes", False
            form = (-(-cfg1.chunk // per), cfg1.head_dim)
        else:
            two = tuple(chunk_shape[-2:])     # k_b / k_sp: (1, Dh, x) -> (Dh, x)
            transposed = two[0] > two[1]
            leaf, form = "side", two[::-1] if transposed else two
        width = _lane_width(form[1])
        rows = _folded_rows(form[0], width)
        fields.append(PageField(name, leaf, used[leaf], rows, form, width,
                                transposed, tuple(chunk_shape), cdt.name))
        used[leaf] += heads * rows
    whole = lambda n, tile: -(-n // tile) * tile
    return PageLayout(heads=cfg1.kv_heads, chunk=cfg1.chunk,
                      bits=cfg1.policy.bits,
                      fields=tuple(fields),
                      codes_rows=whole(used["codes"], 8),
                      side_rows=whole(used["side"], 32 // dtype.itemsize),
                      side_dtype=dtype.name)


def page_layout(cfg: CacheConfig, dtype=jnp.bfloat16) -> PageLayout:
    """The page layout of this layer geometry (see the notes above)."""
    if not paged_supported(cfg):
        raise ValueError(f"paged layout requires a GEAR cache, got {cfg.kind!r}")
    cfg1 = cfg if cfg.batch == 1 else dataclasses.replace(cfg, batch=1)
    return _page_layout(cfg1, jnp.dtype(dtype).name)


def _fold(x: jnp.ndarray, field: PageField) -> jnp.ndarray:
    """Forms ``[..., a, b]`` of ``field`` -> folded rows ``[..., rows,
    128]`` (see the layout notes above)."""
    *lead, a, b = x.shape
    w = field.width
    pad = [(0, 0)] * len(lead)
    if w <= LANES:
        g = LANES // w
        n = _folded_rows(a, w)
        x = jnp.pad(x, pad + [(0, g * n - a), (0, w - b)])
        x = jnp.moveaxis(x.reshape(*lead, g, n, w), -3, -2)
        return x.reshape(*lead, n, LANES)
    m = w // LANES
    x = jnp.pad(x, pad + [(0, 0), (0, w - b)]).reshape(*lead, a, m, LANES)
    return jnp.moveaxis(x, -2, -3).reshape(*lead, m * a, LANES)


def unfold_rows(x: jnp.ndarray, field: PageField) -> jnp.ndarray:
    """Folded rows ``[..., rows, 128]`` of ``field`` (one head's) -> its
    form ``[..., a, b]``; the inverse of :func:`_fold`.  Static slices and
    concatenations only, so the decode kernel reads pages with it too."""
    (a, b), w = field.form, field.width
    if w < LANES:        # rows that shared a 128-lane row: lane groups
        x = jnp.concatenate([x[..., s * w:(s + 1) * w]
                             for s in range(LANES // w)], axis=-2)
    elif w > LANES:      # rows split over 128-lane rows: row groups
        x = jnp.concatenate([x[..., t * a:(t + 1) * a, :]
                             for t in range(w // LANES)], axis=-1)
    return x[..., :a, :b]


def unpack_words(words: jnp.ndarray, bits: int, nb: int) -> jnp.ndarray:
    """Token-major code words ``[..., a, Dh]`` int32 -> codes ``[..., nb,
    Dh]`` int32: bits ``[j·bits, (j+1)·bits)`` of word row ``r`` hold token
    ``r + a·j`` (whole-vreg shifts and masks in the decode kernel; the
    arithmetic shift's sign extension only reaches bits the mask clears)."""
    parts = [(words >> (bits * j)) & (2**bits - 1) for j in range(32 // bits)]
    return jnp.concatenate(parts, axis=-2)[..., :nb, :]


def _codes_to_words(packed: jnp.ndarray, bits: int, a: int) -> jnp.ndarray:
    """Channel-packed codes ``[..., n_b, Dh/per]`` (the dense layout) ->
    token-major words ``[..., a, Dh]`` (inverse of :func:`unpack_words`)."""
    per = 32 // bits
    codes = packing.unpack(packed, bits).astype(jnp.uint32)   # [..., n_b, Dh]
    *lead, nb, d = codes.shape
    codes = jnp.pad(codes, [(0, 0)] * len(lead) + [(0, a * per - nb), (0, 0)])
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[:, None, None]
    words = jnp.sum(codes.reshape(*lead, per, a, d) << shifts, axis=-3,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def chunks_to_pages(layout: PageLayout, fields: dict) -> dict:
    """Dense chunk fields -> page leaves.

    ``fields`` maps every field of ``layout`` to one chunk of every head,
    ``[..., H, *chunk_shape]`` with any leading dims (a payload of
    :func:`extract_prefix_chunks` without its batch axis, stacked); returns
    ``{"codes": [..., codes_rows, 128], "side": [..., side_rows, 128]}``.
    """
    parts: dict[str, list] = {"codes": [], "side": []}
    H = layout.heads
    for f in layout.fields:
        x = fields[f.name]
        lead = x.shape[:x.ndim - 1 - len(f.chunk_shape)]
        x = x.reshape(*lead, H, *f.chunk_shape[-2:])
        if f.leaf == "codes":
            x = _codes_to_words(x.astype(jnp.int32), layout.bits, f.form[0])
        else:
            x = jnp.swapaxes(x, -1, -2) if f.transposed else x
            x = x.astype(layout.side_dtype)
        parts[f.leaf].append(_fold(x, f).reshape(*lead, H * f.rows, LANES))
    out = {}
    for leaf, ((rows, _), _) in layout.leaf_shapes().items():
        x = jnp.concatenate(parts[leaf], axis=-2)
        pad = [(0, 0)] * (x.ndim - 2) + [(0, rows - x.shape[-2]), (0, 0)]
        out[leaf] = jnp.pad(x, pad)
    return out


def pages_to_chunks(layout: PageLayout, leaves: dict) -> dict:
    """Page leaves ``{"codes", "side": [..., rows, 128]}`` -> every field of
    ``layout`` as ``[..., H, *chunk_shape]`` (inverse of
    :func:`chunks_to_pages`, bit for bit)."""
    H = layout.heads
    out = {}
    for f in layout.fields:
        x = leaves[f.leaf]
        lead = x.shape[:-2]
        x = x[..., f.offset:f.offset + H * f.rows, :].reshape(*lead, H, f.rows, LANES)
        x = unfold_rows(x, f)
        if f.leaf == "codes":
            x = packing.pack(unpack_words(x, layout.bits, layout.chunk),
                             layout.bits)
        else:
            x = jnp.swapaxes(x, -1, -2) if f.transposed else x
            x = x.astype(f.chunk_dtype)
        out[f.name] = x.reshape(*lead, H, *f.chunk_shape)
    return out


def gather_pages(layout: PageLayout, codes: jnp.ndarray, side: jnp.ndarray,
                 block_tables: jnp.ndarray) -> dict:
    """Pool leaves ``[P, rows, 128]`` through ``block_tables [B, C]`` ->
    every field of ``layout`` in the dense layout's rows ``[B, H, C·r,
    ...]`` (``r`` rows a chunk).  Shared by :func:`paged_to_dense` and the
    paged oracle (:func:`repro.kernels.ref.gear_decode_paged_ref`)."""
    bt = jnp.asarray(block_tables, jnp.int32)
    chunks = pages_to_chunks(layout, {"codes": codes[bt], "side": side[bt]})
    out = {}
    for name, x in chunks.items():             # [B, C, H, r, ...]
        B, C, H, r, *rest = x.shape
        out[name] = jnp.moveaxis(x, 1, 2).reshape(B, H, C * r, *rest)
    return out


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["codes", "side", "buf_k", "buf_v", "length", "layer"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class PagedGEARLayerCache:
    """GEAR layer cache with pooled chunk storage.

    ``codes`` and ``side`` are the pool's leaves, ``[P, rows, 128]`` (P
    pages shared by every slot; :func:`page_layout` places the fields); the
    streaming buffer ``[B, H, n_b, Dh]`` and ``length [B]`` remain per-slot.
    The block table addressing the pool is *engine-owned metadata* passed
    alongside (like ``pos``), not cache state — it changes only at
    admission/release, never inside a decode step.

    ``layer`` is None, or an int32 scalar when ``codes`` / ``side`` hold
    the pools of every repeat of this layer's pattern position, ``[R, P,
    rows, 128]``, and this cache is repeat ``layer``'s: the decode step's
    layer loop hands each layer the whole stacked pool so that the layer
    reads and writes its pages in place, with no per-layer slice of the
    pool (``models.transformer.decode_tokens``).
    """
    codes: Any
    side: Any
    buf_k: Any
    buf_v: Any
    length: Any
    layer: Any = None


# the dense layout's chunk fields the pool holds
_POOLED_FIELDS = ("k_packed", "k_scale", "k_zero", "v_packed", "v_scale",
                  "v_zero", "k_a", "k_b", "v_a", "v_b",
                  "k_sp_val", "k_sp_idx", "v_sp_val", "v_sp_idx")
_POOL_LEAVES = ("codes", "side")


def paged_supported(cfg: CacheConfig) -> bool:
    """True when this layer's cache can live in the paged pool.

    Any GEAR layout qualifies (the gather path reassembles the dense layout
    bit-for-bit regardless of quant scheme); fp16 and window caches have no
    chunk-decomposable state and stay dense — as do RWKV / SSM recurrent
    states, which the serving layer never pages (DESIGN.md §5).
    """
    return cfg.kind == "gear" and not cfg.policy.is_fp16


def page_field_shapes(cfg: CacheConfig, dtype=jnp.bfloat16) -> dict:
    """``{leaf: (page shape, dtype)}`` of one pool page: ``codes`` and
    ``side``, each ``(rows, 128)`` (:func:`page_layout`)."""
    return page_layout(cfg, dtype).leaf_shapes()


def page_nbytes(cfg: CacheConfig, dtype=jnp.bfloat16) -> int:
    """Bytes of one pool page for ONE layer of this geometry."""
    return sum(rows * lanes * dt.itemsize
               for (rows, lanes), dt in page_field_shapes(cfg, dtype).values())


def init_paged_layer_cache(cfg: CacheConfig, n_pages: int,
                           dtype=jnp.bfloat16) -> PagedGEARLayerCache:
    """Zero pool of ``n_pages`` pages + per-slot buffers for ``cfg.batch``.

    Page 0 is the reserved zero page (never allocated): a fresh cache with
    an all-zero block table gathers back to exactly the dense zero cache.
    """
    if not paged_supported(cfg):
        raise ValueError(f"paged layout requires a GEAR cache, got {cfg.kind!r}")
    if n_pages < 2:
        raise ValueError(f"need >= 2 pages (page 0 is reserved), got {n_pages}")
    B, H, Dh = cfg.batch, cfg.kv_heads, cfg.head_dim
    pool = {leaf: jnp.zeros((n_pages,) + shape, dt)
            for leaf, (shape, dt) in page_field_shapes(cfg, dtype).items()}
    return PagedGEARLayerCache(
        **pool,
        buf_k=jnp.zeros((B, H, cfg.chunk, Dh), dtype),
        buf_v=jnp.zeros((B, H, cfg.chunk, Dh), dtype),
        length=jnp.zeros((B,), jnp.int32),
    )


def _pool_layout(cfg: CacheConfig, pcache: PagedGEARLayerCache) -> PageLayout:
    return page_layout(cfg, pcache.side.dtype)


def layer_pool(pcache: PagedGEARLayerCache) -> dict:
    """``{"codes", "side"}``: this layer's pool leaves ``[P, rows, 128]``
    (sliced out of a stacked pool, a copy that only portable paths take)."""
    if pcache.layer is None:
        return {leaf: getattr(pcache, leaf) for leaf in _POOL_LEAVES}
    return {leaf: getattr(pcache, leaf)[pcache.layer] for leaf in _POOL_LEAVES}


def paged_to_dense(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                   block_tables: jnp.ndarray) -> GEARLayerCache:
    """Gather the pool through ``block_tables [B, C]`` into a dense cache.

    Bitwise equal to the dense-slot layout under the allocator's zero-page
    invariant (unallocated / unwritten table entries point at zeroed
    pages), so the portable decode path is literally ``attend(gather(...))``
    and cache-parity tests can compare arrays directly.
    """
    fields = dict.fromkeys(_POOLED_FIELDS)
    pool = layer_pool(pcache)
    fields.update(gather_pages(_pool_layout(cfg, pcache), pool["codes"],
                               pool["side"], block_tables))
    return GEARLayerCache(**fields, buf_k=pcache.buf_k, buf_v=pcache.buf_v,
                          length=pcache.length)


def gather_pool_chunks(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                       pages: jnp.ndarray) -> list[dict]:
    """Read pool pages into per-chunk payload dicts (batch-1 layout).

    The inverse of :func:`scatter_pool_chunks`: each payload field carries
    the ``[1, ...]`` batch axis :func:`splice_prefix_chunks` expects, so a
    prefix-cache hit gathers its pages straight into the batch-1 scaffold.
    """
    pages = jnp.asarray(pages, jnp.int32)
    chunks = pages_to_chunks(_pool_layout(cfg, pcache),
                             {leaf: x[pages]
                              for leaf, x in layer_pool(pcache).items()})
    return [{name: x[c][None] for name, x in chunks.items()}
            for c in range(pages.shape[0])]


def _set_pages(pcache: PagedGEARLayerCache, pages: jnp.ndarray,
               leaves: dict) -> PagedGEARLayerCache:
    """Write page leaves ``[n, rows, 128]`` at ``pages [n]`` (of this
    layer's pool, in place in a stacked one); out-of-range page ids drop
    their write."""
    at = pages if pcache.layer is None else (pcache.layer, pages)
    return dataclasses.replace(pcache, **{
        leaf: getattr(pcache, leaf).at[at].set(
            leaves[leaf].astype(getattr(pcache, leaf).dtype), mode="drop")
        for leaf in _POOL_LEAVES})


def scatter_pool_chunks(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                        pages: jnp.ndarray,
                        chunks: list[dict]) -> PagedGEARLayerCache:
    """Write per-chunk payload dicts (``extract_prefix_chunks`` layout,
    batch-1) into pool pages ``pages [len(chunks)]`` — the paged half of the
    slot-splice protocol: a batch-1 prefill's closed chunks become the
    slot's pages.  Out-of-range page ids drop the write.
    """
    if not chunks:
        return pcache
    layout = _pool_layout(cfg, pcache)
    fields = {f.name: jnp.stack([ch[f.name][0] for ch in chunks])
              for f in layout.fields}
    return _set_pages(pcache, jnp.asarray(pages, jnp.int32),
                      chunks_to_pages(layout, fields))


def zero_pool_pages(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                    pages: jnp.ndarray) -> PagedGEARLayerCache:
    """Zero the given pool pages — run at admission on freshly allocated
    pages so exposed-but-unwritten block-table entries keep gathering the
    dense layout's zeros (the bit-parity invariant; DESIGN.md §5)."""
    pages = jnp.asarray(pages, jnp.int32)
    return _set_pages(pcache, pages, {
        leaf: jnp.zeros((pages.shape[0],) + getattr(pcache, leaf).shape[-2:])
        for leaf in _POOL_LEAVES})


def append_token_paged(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                       block_tables: jnp.ndarray, k_t: jnp.ndarray,
                       v_t: jnp.ndarray, key: jax.Array | None = None):
    """Paged twin of :func:`append_token`: same buffer writes and the same
    slot-invariant compression event, but a closing chunk is converted to
    page form and scattered into the slot's block-table page instead of
    dense batch rows.  Slots not at a chunk boundary (or past capacity)
    redirect the page index out of bounds and the scatter drops — one
    batched write serves every phase mix.
    """
    pol = cfg.policy
    nb = cfg.chunk
    if key is None:
        key = jax.random.PRNGKey(0)
    bt = jnp.asarray(block_tables, jnp.int32)
    buf_pos = pcache.length % nb
    buf_k = _slot_rows_update(pcache.buf_k, k_t[:, :, None, :], buf_pos)
    buf_v = _slot_rows_update(pcache.buf_v, v_t[:, :, None, :], buf_pos)
    pcache = dataclasses.replace(pcache, buf_k=buf_k, buf_v=buf_v,
                                 length=pcache.length + 1)

    def compress(c):
        need = (c.length % nb == 0) & (c.length > 0) & (c.length <= cfg.capacity)
        cidx = jnp.clip(jnp.maximum(c.length - 1, 0) // nb, 0, cfg.n_chunks - 1)
        P = c.codes.shape[-3]
        page = jnp.take_along_axis(bt, cidx[:, None], axis=1)[:, 0]
        # page 0 is the reserved zero page: an idle slot (all-zero table
        # row) crossing a buffer boundary must drop its write rather than
        # corrupt the invariant every slot's out-of-extent reads depend on
        page = jnp.where(need & (page > 0), page, P)   # OOB -> scatter drops
        B, H, _, Dh = c.buf_k.shape
        kc = c.buf_k[:, :, None, :, :].astype(jnp.float32)
        vc = c.buf_v[:, :, None, :, :].astype(jnp.float32)
        # same slot-/step-invariant key as the dense path: a paged slot's
        # chunk is bit-identical to the dense slot's (splice isolation)
        comp = _compress_chunks(cfg, kc, vc, pol.rank_decode, key)
        fields = {"k_packed": comp["k_packed"].reshape(B, H, nb, -1),
                  "v_packed": comp["v_packed"].reshape(B, H, nb, -1)}
        for kv in ("k", "v"):
            fields[f"{kv}_scale"] = _flatten_stat(cfg, comp[f"{kv}_scale"], kv)
            fields[f"{kv}_zero"] = _flatten_stat(cfg, comp[f"{kv}_zero"], kv)
            if pol.use_lowrank:
                fields[f"{kv}_a"] = comp[f"{kv}_a"].reshape(B, H, nb, pol.rank)
                fields[f"{kv}_b"] = comp[f"{kv}_b"]
            if pol.use_sparse:
                for part in ("val", "idx"):
                    x = comp[f"{kv}_sp_{part}"]
                    if kv == "v" or cfg.k_scheme()[0] != "per_channel":
                        x = x.reshape(B, H, nb, x.shape[-1])
                    fields[f"{kv}_sp_{part}"] = x
        return _set_pages(c, page,
                          chunks_to_pages(_pool_layout(cfg, c), fields))

    any_boundary = jnp.any((pcache.length % nb == 0) & (pcache.length > 0)
                           & (pcache.length <= cfg.capacity))
    return jax.lax.cond(any_boundary, compress, lambda c: c, pcache)


def attend_paged(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                 block_tables: jnp.ndarray, q: jnp.ndarray, scale: float,
                 use_factored: bool = True) -> jnp.ndarray:
    """Portable paged decode attention: gather pages to the dense layout,
    then the standard factored :func:`attend` — identical values in
    identical shapes, so the result is bit-identical to the dense path.
    The fused twin (:func:`repro.kernels.ops.gear_attend_paged`) gathers by
    table index inside the kernel grid instead."""
    return attend(cfg, paged_to_dense(cfg, pcache, block_tables), q, scale,
                  use_factored=use_factored)


# ---------------------------------------------------------------------------
# Slot splicing (continuous batching)


def splice_slot(full, one, slot, axis: int = 0):
    """Write a batch-1 cache pytree ``one`` into batch row ``slot`` of ``full``.

    Works on any cache pytree whose leaves carry the batch dim at ``axis``
    (``axis=0`` for a single layer cache, ``axis=1`` for the engine's
    repeat-stacked ``[R, B, ...]`` trees — including RWKV/SSM states).
    ``slot`` may be a traced scalar, so one jitted program serves every slot.
    """
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), slot, axis=axis),
        full, one)


@functools.lru_cache(maxsize=64)
def _fresh_batch1_cached(cfg1: CacheConfig, dtype_name: str):
    return init_layer_cache(cfg1, jnp.dtype(dtype_name))


def fresh_batch1_cache(cfg: CacheConfig, dtype=jnp.bfloat16):
    """Memoized empty batch-1 cache for ``cfg``'s geometry.

    ``CacheConfig`` is hashable (frozen dataclasses all the way down), so
    the zero tree is built once per geometry instead of on every splice —
    :func:`reset_slot` / :func:`prefill_into_slot` sit on the continuous-
    batching per-request path and used to reallocate it each call.  The
    returned tree is shared: callers must treat it as read-only (splices
    copy out of it; never donate it into a jitted program).
    """
    cfg1 = cfg if cfg.batch == 1 else dataclasses.replace(cfg, batch=1)
    return _fresh_batch1_cached(cfg1, jnp.dtype(dtype).name)


def reset_slot(cfg: CacheConfig, cache, slot, dtype=jnp.bfloat16):
    """Return ``cache`` with batch row ``slot`` back in the empty state.

    Length goes to 0 (and window ``pos`` to -1), so every attend mask treats
    the slot as empty; stale K/V bytes are also zeroed for hygiene.
    """
    return splice_slot(cache, fresh_batch1_cache(cfg, dtype), slot)


def prefill_into_slot(cfg: CacheConfig, cache, k: jnp.ndarray, v: jnp.ndarray,
                      slot, key: jax.Array | None = None, dtype=jnp.bfloat16):
    """Prefill one request's K/V [1, H, n, Dh] into batch row ``slot``.

    The single-request cache is built exactly as a batch-1 prefill would
    build it (same chunking, same compression keys), then spliced over the
    slot — the cache-level half of the slot-splice protocol (DESIGN.md).
    The empty batch-1 scaffold comes from the :func:`fresh_batch1_cache`
    memo, so the per-request path allocates only the filled tree.
    """
    cfg1 = dataclasses.replace(cfg, batch=1)
    one = prefill_layer_cache(cfg1, fresh_batch1_cache(cfg1, dtype), k, v, key)
    return splice_slot(cache, one, slot)
