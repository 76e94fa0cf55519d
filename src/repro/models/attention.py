"""Multi-head attention: GQA/MQA, RoPE, QK-norm, logit softcap, sliding
window, prefix-LM; full-sequence (train/prefill) and cached-decode paths.

The full-sequence path chunks queries with ``lax.scan`` so the score matrix
never exceeds ``[B, H, q_chunk, S]`` — required for the 32k prefill shapes.
The decode path runs against any :mod:`repro.core.cache` layer cache (GEAR,
fp16, or sliding-window ring buffer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.kernels import ops as kernel_ops
from repro.models.common import KeyGen, apply_rope, dense_init, rmsnorm

__all__ = ["attn_params", "attention_train", "attention_decode",
           "attention_prefill_streaming", "streaming_prefill_supported",
           "rope_theta_for"]

NEG_INF = -1e30


def rope_theta_for(cfg: ModelConfig, kind: str) -> float:
    # gemma3-style dual RoPE: local layers use short-range theta.
    if kind == "local" and cfg.attn_pattern == "local_global":
        return 10_000.0
    return cfg.rope_theta


def attn_params(cfg: ModelConfig, kg: KeyGen) -> dict:
    d, qd, kvd, dh = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    p = {
        "wq": dense_init(kg(), (d, qd)),
        "wk": dense_init(kg(), (d, kvd)),
        "wv": dense_init(kg(), (d, kvd)),
        "wo": dense_init(kg(), (qd, d), fan_in=qd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def _project_qkv(cfg: ModelConfig, params, x, positions, kind: str):
    B, S, _ = x.shape
    dh = cfg.head_dim
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads, dh)
    k = (x @ params["wk"].astype(x.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    v = (x @ params["wv"].astype(x.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])
    theta = rope_theta_for(cfg, kind)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _mask(q_pos, k_pos, kind: str, window: int, prefix_len: int):
    """[... , Sq, Sk] additive-mask boolean: True = attend."""
    causal = q_pos[:, None] >= k_pos[None, :]
    ok = causal
    if kind == "local":
        ok = ok & (q_pos[:, None] - k_pos[None, :] < window)
    if prefix_len:
        both_prefix = (q_pos[:, None] < prefix_len) & (k_pos[None, :] < prefix_len)
        ok = ok | both_prefix
    return ok


def _sdpa_chunked(cfg: ModelConfig, q, k, v, positions, kind: str,
                  prefix_len: int, q_chunk: int):
    """q: [B,S,Hq,Dh]; k,v: [B,S,Hkv,Dh] -> [B,S,Hq,Dh].  Scans q chunks."""
    B, S, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = Dh ** -0.5
    cap = cfg.attn_logit_softcap
    kT = jnp.moveaxis(k, 1, 2)  # [B,Hkv,S,Dh]
    vT = jnp.moveaxis(v, 1, 2)
    k_pos = positions

    def block(q_blk, pos_blk):
        # q_blk: [B, qc, Hq, Dh].  Scores/probs materialize bf16 (MXU
        # accumulates f32 internally); softmax internals run f32 fused —
        # the standard TPU mixed-precision attention layout.
        qg = jnp.moveaxis(q_blk, 1, 2).reshape(B, Hkv, G, q_blk.shape[1], Dh)
        s = jnp.einsum("bhgqd,bhsd->bhgqs", qg.astype(jnp.bfloat16),
                       kT.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16) * scale
        if cap:
            s = (cap * jnp.tanh(s.astype(jnp.float32) / cap)).astype(jnp.bfloat16)
        m = _mask(pos_blk, k_pos, kind, cfg.local_window, prefix_len)
        s = jnp.where(m[None, None, None], s, jnp.bfloat16(NEG_INF))
        mx = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        ex = jnp.exp((s - mx).astype(jnp.float32))
        w = (ex / jnp.sum(ex, axis=-1, keepdims=True)).astype(jnp.bfloat16)
        # bf16 output materialization: the MXU still accumulates f32
        # internally, and this keeps the transposed (backward) dot's
        # cotangent bf16 too (§Perf iteration 3).
        o = jnp.einsum("bhgqs,bhsd->bhgqd", w, vT.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16)
        return jnp.moveaxis(o.reshape(B, Hq, q_blk.shape[1], Dh), 1, 2)

    if S <= q_chunk:
        return block(q, positions).astype(q.dtype)
    assert S % q_chunk == 0, (S, q_chunk)
    nblk = S // q_chunk
    q_blocks = jnp.moveaxis(q.reshape(B, nblk, q_chunk, Hq, Dh), 1, 0)
    pos_blocks = positions.reshape(nblk, q_chunk)
    _, out = jax.lax.scan(lambda c, xs: (c, block(*xs)), None, (q_blocks, pos_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, Dh).astype(q.dtype)


def _sdpa_flash(cfg: ModelConfig, q, k, v, kind: str, prefix_len: int,
                interpret: bool):
    """Full-sequence attention through the ``flash_prefill`` Pallas kernel.

    q: [B,S,Hq,Dh]; k,v: [B,S,Hkv,Dh] -> [B,S,Hq,Dh].  GQA lays query rows
    out (B, Hkv, G) head-major and the kernel's ``kv_repeat`` index map
    points each group at its shared K/V row — no G-fold broadcast copy of
    K/V is ever materialized.  Same mask family as ``_sdpa_chunked``
    (causal / sliding window / bidirectional prefix, plus logit softcap).
    Under a mesh the kernel runs per head shard
    (:func:`repro.kernels.ops.shard_over_heads`).
    """
    G = q.shape[2] // k.shape[2]
    window = cfg.local_window if kind == "local" else 0

    def local(q, k, v):
        B, S, Hq, Dh = q.shape
        Hkv = k.shape[2]
        qh = jnp.moveaxis(q, 1, 2).reshape(B * Hq, S, Dh)   # (B, Hkv, G) rows
        out = kernel_ops.flash_attention(
            qh, jnp.moveaxis(k, 1, 2).reshape(B * Hkv, S, Dh),
            jnp.moveaxis(v, 1, 2).reshape(B * Hkv, S, Dh),
            window=window, prefix_len=prefix_len,
            softcap=cfg.attn_logit_softcap, kv_repeat=G, interpret=interpret)
        return jnp.moveaxis(out.reshape(B, Hq, S, Dh), 1, 2)

    return kernel_ops.shard_over_heads(local, (q, k, v), head_axis=2,
                                       n_heads=k.shape[2])


def attention_train(cfg: ModelConfig, params, x, positions, kind: str = "global",
                    prefix_len: int = 0, q_chunk: int = 512,
                    impl: str = "chunked"):
    """Full-sequence attention (train / prefill).  Returns (out, (k, v)).

    k/v are returned [B, Hkv, S, Dh] for optional cache construction.
    ``impl`` selects the score path: "chunked" (lax.scan'd XLA blocks — the
    training default), "flash" (the ``flash_prefill`` Pallas kernel — the
    monolithic-prefill fast path on TPU), or "flash-interpret" (kernel in
    interpret mode, CI parity lane).
    """
    q, k, v = _project_qkv(cfg, params, x, positions, kind)
    if impl in ("flash", "flash-interpret"):
        out = _sdpa_flash(cfg, q, k, v, kind, prefix_len,
                          interpret=impl == "flash-interpret")
    else:
        out = _sdpa_chunked(cfg, q, k, v, positions, kind, prefix_len, q_chunk)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.q_dim).astype(x.dtype) @ params["wo"].astype(x.dtype)
    return out, (jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))


def streaming_prefill_supported(cfg: ModelConfig, kind: str,
                                cache_cfg) -> bool:
    """Layers that can take the streaming chunked-prefill pipeline.

    Requires the streaming cache layout (GEAR with per-channel K stats at
    chunk granularity — :func:`repro.core.cache.streaming_supported`; fp16
    has no compression event, window layers keep their ring buffer) and
    plain causal attention: the factored history scores have no
    sliding-window or bidirectional-prefix mask, and — like the
    cached-decode path — no logit softcap.  Unsupported layers fall back
    to monolithic prefill under the same knob.  Static (config-only), so
    the dispatch never splits a jitted program.
    """
    return (cache_lib.streaming_supported(cache_cfg) and kind != "local"
            and cfg.attn_logit_softcap == 0.0
            and not (cfg.modality == "vlm" and cfg.num_prefix_tokens))


def attention_prefill_streaming(cfg: ModelConfig, params, x, positions,
                                kind: str, cache_cfg, key=None,
                                fused: str = "auto", dtype=jnp.bfloat16,
                                cache=None, start_pos: int = 0,
                                padded_tail: bool = False, true_len=None):
    """Streaming chunked prefill of one attention layer: project → compress
    → attend, one ``n_b``-token chunk at a time under two carry-free
    ``lax.scan`` passes (loop fission of the compress-as-you-go pipeline —
    see :func:`repro.core.cache.streaming_prefill_layer_cache`).

    Q/K/V are projected *per chunk inside the scans*, so the full-sequence
    FP16 K/V never exists: peak memory is the compressed cache plus one
    chunk of K/V and scores.  The compression scan closes every chunk
    through the (optionally fused) compression event and its stacked
    outputs are stored once; the attend scan then runs each chunk's
    queries against the compressed history *before* that chunk (scores
    masked at ``c · n_b``) plus the in-flight FP16 chunk via a two-piece
    online softmax — the same semantics decode already has (compressed
    history + FP16 buffer).  Leftover tokens land in the streaming buffer.
    Returns (out [B, S, d_model], layer cache); the cache is bit-identical
    to a monolithic prefill of the same tokens.

    ``start_pos`` > 0 (with ``cache`` holding ``start_pos / n_b`` chunks
    already spliced from the prefix cache) runs the suffix path: ``x`` /
    ``positions`` cover only the tokens after the cached prefix, new
    chunks are stored from that offset, and every attend sees the cached
    chunks as compressed history — bit-identical to the cold prefill that
    would have computed them (DESIGN.md §4).

    ``padded_tail=True`` (with ``true_len`` the traced real token count)
    marks ``x`` as length-bucketed: ``S`` is a chunk multiple whose last
    ``n_b`` block is right-padded.  That block stays out of the compression
    scan and lands in the FP16 streaming buffer; see
    :func:`repro.core.cache.streaming_prefill_pipeline`.
    """
    B, S, _ = x.shape
    nb = cache_cfg.chunk
    if start_pos % nb:
        raise ValueError(f"start_pos {start_pos} not aligned to chunk {nb}")
    if padded_tail and S % nb:
        raise ValueError(f"padded_tail needs S % n_b == 0 (S={S}, n_b={nb})")
    scale = cfg.head_dim ** -0.5
    if cache is None:
        cache = cache_lib.init_layer_cache(cache_cfg, dtype)
    C_new = S // nb - 1 if padded_tail else S // nb
    n_full = C_new * nb

    def project(x_blk_pos):
        x_blk, pos_blk = x_blk_pos
        q, k, v = _project_qkv(cfg, params, x_blk, pos_blk, kind)
        return (jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                jnp.moveaxis(v, 1, 2))                       # [B, H*, T, Dh]

    chunk_xs = None
    if C_new:
        chunk_xs = (jnp.moveaxis(x[:, :n_full].reshape(B, C_new, nb, -1), 1, 0),
                    positions[:n_full].reshape(C_new, nb))
    tail_x = (x[:, n_full:], positions[n_full:]) if S > n_full else None
    cache, out = cache_lib.streaming_prefill_pipeline(
        cache_cfg, cache, S, chunk_xs, tail_x, project, scale, key, fused,
        start_chunk=start_pos // nb, tail_is_padded=padded_tail,
        true_n=true_len)
    out = jnp.moveaxis(out, 1, 2).reshape(B, S, cfg.q_dim).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), cache


def attention_decode(cfg: ModelConfig, params, x_t, pos, cache, cache_cfg,
                     kind: str = "global", fused: str = "auto",
                     block_tables=None):
    """One-token attention against a layer cache.

    x_t: [B, 1, d]; pos: int32 absolute position — scalar (all slots aligned)
    or [B] (per-slot positions, continuous batching).  Both shapes go through
    the same per-slot RoPE path so wave-mode and spliced-slot decodes are
    bit-identical per batch row.

    ``fused`` selects the attend path for GEAR caches in the fused-kernel
    layout (:func:`repro.kernels.ops.fused_supported`):
      "auto"      — fused :func:`repro.kernels.ops.gear_attend` (Pallas
                    kernel on TPU, jnp oracle elsewhere); ragged-aware, so
                    mixed-length continuous batches take it too;
      "interpret" — force the Pallas kernel in interpret mode (CI kernel
                    lane: exercises kernel code through the serving stack);
      "off"       — the portable :func:`repro.core.cache.attend` path.
    The choice is static (layout-based, never length-based) so wave and
    continuous modes share one numeric program per configuration.

    A :class:`~repro.core.cache.PagedGEARLayerCache` takes the same paths
    with its pooled twins (``append_token_paged`` + ``gear_attend_paged`` /
    ``attend_paged``); ``block_tables [B, C]`` is required then — it is
    engine-owned metadata like ``pos``, threaded per call rather than
    stored in the cache.  Returns (out [B, 1, d], new_cache).
    """
    B = x_t.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape((-1, 1)), (B, 1))
    q, k, v = _project_qkv(cfg, params, x_t, positions, kind)
    k_t = jnp.squeeze(k, axis=1)  # [B, Hkv, Dh]
    v_t = jnp.squeeze(v, axis=1)
    q_t = jnp.squeeze(q, axis=1)  # [B, Hq, Dh]
    scale = cfg.head_dim ** -0.5
    # NOTE: logit softcap is omitted on the cached-decode path (it only
    # matters for training stability); documented in DESIGN.md.
    if isinstance(cache, cache_lib.PagedGEARLayerCache):
        if block_tables is None:
            raise ValueError("paged cache decode needs block_tables")
        with jax.named_scope("cache_update"):
            new_cache = cache_lib.append_token_paged(cache_cfg, cache,
                                                     block_tables, k_t, v_t)
        if fused != "off" and kernel_ops.fused_supported(cache_cfg):
            out = kernel_ops.gear_attend_paged(
                cache_cfg, new_cache, block_tables, q_t, scale=scale,
                force_kernel=fused == "interpret",
                interpret=fused == "interpret")
        else:
            out = cache_lib.attend_paged(cache_cfg, new_cache, block_tables,
                                         q_t, scale)
        out = out.reshape(B, 1, cfg.q_dim) @ params["wo"].astype(x_t.dtype)
        return out, new_cache
    with jax.named_scope("cache_update"):
        new_cache = cache_lib.append_token(cache_cfg, cache, k_t, v_t)
    if fused != "off" and kernel_ops.fused_supported(cache_cfg):
        out = kernel_ops.gear_attend(cache_cfg, new_cache, q_t,
                                     scale=scale,
                                     force_kernel=fused == "interpret",
                                     interpret=fused == "interpret")
    else:
        out = cache_lib.attend(cache_cfg, new_cache, q_t, scale=scale)
    out = out.reshape(B, 1, cfg.q_dim) @ params["wo"].astype(x_t.dtype)
    return out, new_cache
