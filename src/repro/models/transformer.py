"""Decoder assembly for all assigned architectures.

The layer stack is organized as ``pattern_repeats`` repetitions of a short
``layer_pattern`` unit (e.g. gemma3: LLLLLG ×8; uniform archs: unit of 1).
Parameters and per-layer caches are **stacked over repeats** and the stack is
driven by ``lax.scan`` — compile time stays O(pattern) instead of O(layers),
which is what makes the 94-layer qwen3 dry-run compile quickly.

Block families:
  attn   — [hybrid: ∥ SSM] attention + (MLP | MoE)
  rwkv   — RWKV6 time-mix + channel-mix (attention-free)

Modes: ``train`` (full seq, no cache), ``prefill`` (full seq → caches),
``decode`` (one token, cache update).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core.policy import CompressionPolicy
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.common import KeyGen, apply_norm, dense_init, norm_params
from repro.models.mlp import mlp_apply, mlp_params
from repro.models.moe import moe_apply, moe_params

__all__ = [
    "init_params", "block_params", "forward", "decode_tokens",
    "init_caches", "cache_cfg_for", "pick_q_chunk", "embed_tokens", "logits_from_hidden",
]


def pick_q_chunk(s: int, target: int = 512) -> int:
    c = min(target, s)
    while s % c:
        c //= 2
    return max(c, 1)


# ---------------------------------------------------------------------------
# Parameters


def block_params(cfg: ModelConfig, kg: KeyGen, kind: str) -> dict:
    if kind == "rwkv":
        return {
            "ln1": norm_params(cfg.d_model, "layernorm"),
            "ln2": norm_params(cfg.d_model, "layernorm"),
            **rwkv_lib.rwkv_params(cfg, kg),
        }
    p = {
        "ln1": norm_params(cfg.d_model, cfg.norm),
        "attn": attn_lib.attn_params(cfg, kg),
        "ln2": norm_params(cfg.d_model, cfg.norm),
    }
    if cfg.moe:
        p["moe"] = moe_params(cfg, kg)
    else:
        p["mlp"] = mlp_params(cfg, kg)
    if cfg.ssm and cfg.hybrid_parallel:
        p["ssm"] = ssm_lib.ssm_params(cfg, kg)
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    kg = KeyGen(key)
    d, v = cfg.d_model, cfg.vocab_size
    params: dict[str, Any] = {}
    if cfg.modality == "audio":
        params["embed"] = dense_init(kg(), (cfg.num_codebooks, v, d), fan_in=d)
    else:
        params["embed"] = dense_init(kg(), (v, d), fan_in=d)
    if not cfg.tie_embeddings:
        head_v = v * cfg.num_codebooks if cfg.modality == "audio" else v
        params["lm_head"] = dense_init(kg(), (d, head_v))
    params["final_norm"] = norm_params(d, cfg.norm)

    R = cfg.pattern_repeats
    blocks = []
    for kind in cfg.layer_pattern:
        keys = jax.random.split(kg(), R)
        stacked = jax.vmap(lambda k: block_params(cfg, KeyGen(k), kind))(keys)
        blocks.append(stacked)
    params["blocks"] = tuple(blocks)
    return params


# ---------------------------------------------------------------------------
# Embedding / head


# Activations run in bf16 (mixed precision: f32 master params, f32 norm/
# softmax internals).  Halves every dot operand's HBM traffic — see
# EXPERIMENTS.md §Perf iteration 1.
COMPUTE_DTYPE = jnp.bfloat16


def embed_tokens(cfg: ModelConfig, params, batch: dict) -> jnp.ndarray:
    scale = cfg.d_model ** 0.5 if cfg.mlp_kind == "geglu" else 1.0
    if cfg.modality == "audio":
        toks = batch["tokens"]  # [B, S, K]
        emb = params["embed"]   # [K, V, d]
        x = sum(jnp.take(emb[i], toks[..., i], axis=0) for i in range(cfg.num_codebooks))
    elif cfg.modality == "vlm" and "img_embeds" in batch:
        txt = jnp.take(params["embed"], batch["tokens"], axis=0) * scale
        x = jnp.concatenate([batch["img_embeds"].astype(txt.dtype), txt], axis=1)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0) * scale
    return x.astype(COMPUTE_DTYPE)


def logits_from_hidden(cfg: ModelConfig, params, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        emb = params["embed"]
        if cfg.modality == "audio":
            out = jnp.einsum("bsd,kvd->bskv", h, emb.astype(h.dtype))
            return out
        return h @ emb.astype(h.dtype).T
    out = h @ params["lm_head"].astype(h.dtype)
    if cfg.modality == "audio":
        return out.reshape(out.shape[:-1] + (cfg.num_codebooks, cfg.vocab_size))
    return out


# ---------------------------------------------------------------------------
# Caches


def cache_cfg_for(cfg: ModelConfig, kind: str, policy: CompressionPolicy,
                  batch: int, capacity: int) -> cache_lib.CacheConfig:
    if kind == "local":
        return cache_lib.CacheConfig(
            batch=batch, kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            capacity=min(capacity, cfg.local_window), policy=policy,
            kind="window", window=cfg.local_window)
    return cache_lib.CacheConfig(
        batch=batch, kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        capacity=capacity, policy=policy,
        kind="fp16" if policy.is_fp16 else "gear")


def _unit_cache(cfg: ModelConfig, kind: str, policy, batch, capacity, dtype,
                layout: str = "dense", pool_pages: int = 0):
    """Zero cache object for ONE layer of the given kind.

    ``layout="paged"`` puts GEAR-compressible attention layers into the
    pooled page layout (:class:`~repro.core.cache.PagedGEARLayerCache`,
    ``pool_pages`` pages).  Window ring buffers, fp16 caches, and RWKV/SSM
    recurrent state have no chunk decomposition and stay dense inside a
    mixed tree — the documented fallback (DESIGN.md §5).
    """
    if kind == "rwkv":
        return rwkv_lib.init_rwkv_state(cfg, batch, dtype)
    ccfg = cache_cfg_for(cfg, kind, policy, batch, capacity)
    if layout == "paged" and cache_lib.paged_supported(ccfg):
        c = cache_lib.init_paged_layer_cache(ccfg, pool_pages, dtype)
    else:
        c = cache_lib.init_layer_cache(ccfg, dtype)
    if cfg.ssm and cfg.hybrid_parallel:
        return (c, ssm_lib.init_ssm_state(cfg, batch, dtype))
    return c


def init_caches(cfg: ModelConfig, policy: CompressionPolicy, batch: int,
                capacity: int, dtype=jnp.bfloat16, layout: str = "dense",
                pool_pages: int = 0):
    """Tuple over pattern positions of caches stacked over repeats [R, ...].

    ``layout="paged"`` gives every paged-capable position a page pool leaf
    ``[R, pool_pages, ...]``: each repeat of each position has its own
    pool, all addressed by ONE engine-owned block table ``[B, C]`` (page
    id ``p`` means page ``p`` in every layer's pool — that is what makes
    the allocator a single global byte-budgeted pool).
    """
    if layout not in ("dense", "paged"):
        raise ValueError(f"layout must be dense/paged, got {layout!r}")
    if layout == "paged" and pool_pages < 2:
        raise ValueError("paged layout needs pool_pages >= 2 "
                         "(page 0 is the reserved zero page)")
    R = cfg.pattern_repeats
    out = []
    for kind in cfg.layer_pattern:
        one = _unit_cache(cfg, kind, policy, batch, capacity, dtype,
                          layout=layout, pool_pages=pool_pages)
        out.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (R,) + x.shape), one))
    return tuple(out)


# ---------------------------------------------------------------------------
# Blocks


def _apply_block_train(cfg: ModelConfig, bp, x, kind, positions, prefix_len,
                       q_chunk, want_kv: bool, attn_impl: str = "chunked"):
    """Returns (x, aux, cache_or_kv)."""
    if kind == "rwkv":
        h, (shift_tm, wkv) = rwkv_lib.time_mix_apply(cfg, bp, apply_norm(x, bp["ln1"], "layernorm"))
        x = x + h
        h, shift_cm = rwkv_lib.channel_mix_apply(cfg, bp, apply_norm(x, bp["ln2"], "layernorm"))
        x = x + h
        st = rwkv_lib.RWKVState(shift_tm=shift_tm.astype(jnp.bfloat16),
                                shift_cm=shift_cm.astype(jnp.bfloat16), wkv=wkv)
        return x, jnp.zeros((), jnp.float32), st if want_kv else None

    xin = apply_norm(x, bp["ln1"], cfg.norm)
    h, (k, v) = attn_lib.attention_train(cfg, bp["attn"], xin, positions, kind,
                                         prefix_len, q_chunk, impl=attn_impl)
    ssm_state = None
    if cfg.ssm and cfg.hybrid_parallel:
        h2, ssm_state = ssm_lib.ssm_apply(cfg, bp["ssm"], xin)
        h = (h + h2) * 0.5
    x = x + h
    xin2 = apply_norm(x, bp["ln2"], cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe:
        m, aux = moe_apply(cfg, bp["moe"], xin2)
    else:
        m = mlp_apply(cfg, bp["mlp"], xin2)
    x = x + m
    kv_out = None
    if want_kv:
        kv_out = ((k, v), ssm_state) if ssm_state is not None else (k, v)
    return x, aux, kv_out


def _apply_block_decode(cfg: ModelConfig, bp, x_t, kind, pos, cache, policy,
                        batch, capacity, fused: str = "auto",
                        block_tables=None):
    if kind == "rwkv":
        h, cache = rwkv_lib.time_mix_decode(cfg, bp, apply_norm(x_t, bp["ln1"], "layernorm"), cache)
        x_t = x_t + h
        h, cache = rwkv_lib.channel_mix_decode(cfg, bp, apply_norm(x_t, bp["ln2"], "layernorm"), cache)
        return x_t + h, cache

    hybrid = cfg.ssm and cfg.hybrid_parallel
    attn_cache, ssm_state = (cache if hybrid else (cache, None))
    ccfg = cache_cfg_for(cfg, kind, policy, batch, capacity)
    xin = apply_norm(x_t, bp["ln1"], cfg.norm)
    with jax.named_scope("attention"):
        h, attn_cache = attn_lib.attention_decode(
            cfg, bp["attn"], xin, pos, attn_cache, ccfg, kind, fused=fused,
            block_tables=block_tables)
    if hybrid:
        h2, ssm_state = ssm_lib.ssm_decode(cfg, bp["ssm"], xin, ssm_state)
        h = (h + h2) * 0.5
    x_t = x_t + h
    xin2 = apply_norm(x_t, bp["ln2"], cfg.norm)
    with jax.named_scope("mlp"):
        m = (moe_apply(cfg, bp["moe"], xin2)[0] if cfg.moe
             else mlp_apply(cfg, bp["mlp"], xin2))
    x_t = x_t + m
    new_cache = (attn_cache, ssm_state) if hybrid else attn_cache
    return x_t, new_cache


def _apply_block_prefill(cfg: ModelConfig, bp, x, kind, positions, prefix_len,
                         q_chunk, policy, batch, capacity, cache_dtype,
                         fused: str, attn_impl: str, cache=None,
                         start_pos: int = 0, padded_tail: bool = False,
                         true_len=None):
    """Prefill block that builds its layer cache directly (streaming mode).

    Layers supporting the streaming pipeline project/attend/compress chunk
    by chunk (the full-sequence FP16 K/V never exists); window / softcap /
    prefix-LM / fp16 layers fall back to monolithic attention with the
    batched compression event, inside the same unit body.  Suffix prefill
    (``start_pos`` > 0, ``cache`` pre-populated with the cached prefix
    chunks) has no such fallback: every layer must take the streaming
    pipeline, since only it can see the prefix in compressed form.
    Returns (x, aux, cache)."""
    if kind == "rwkv":
        if start_pos or padded_tail:
            raise ValueError("suffix/bucketed prefill cannot resume an "
                             "RWKV state")
        return _apply_block_train(cfg, bp, x, kind, positions, prefix_len,
                                  q_chunk, want_kv=True)
    ccfg = cache_cfg_for(cfg, kind, policy, batch, capacity)
    if not attn_lib.streaming_prefill_supported(cfg, kind, ccfg):
        if start_pos or padded_tail:
            raise ValueError(
                f"suffix/bucketed prefill requires every layer to support "
                f"the streaming pipeline (kind={kind!r} does not)")
        x, aux, kv = _apply_block_train(cfg, bp, x, kind, positions, prefix_len,
                                        q_chunk, want_kv=True,
                                        attn_impl=attn_impl)
        return x, aux, _kv_to_cache(cfg, kind, kv, policy, batch, capacity,
                                    cache_dtype)
    xin = apply_norm(x, bp["ln1"], cfg.norm)
    with jax.named_scope("attention"):
        h, cache = attn_lib.attention_prefill_streaming(
            cfg, bp["attn"], xin, positions, kind, ccfg, fused=fused,
            dtype=cache_dtype, cache=cache, start_pos=start_pos,
            padded_tail=padded_tail, true_len=true_len)
    ssm_state = None
    if cfg.ssm and cfg.hybrid_parallel:
        if start_pos or padded_tail:
            raise ValueError("suffix/bucketed prefill cannot resume a "
                             "hybrid SSM state")
        h2, ssm_state = ssm_lib.ssm_apply(cfg, bp["ssm"], xin)
        h = (h + h2) * 0.5
    x = x + h
    xin2 = apply_norm(x, bp["ln2"], cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if cfg.moe:
            m, aux = moe_apply(cfg, bp["moe"], xin2)
        else:
            m = mlp_apply(cfg, bp["mlp"], xin2)
    x = x + m
    if ssm_state is not None:
        return x, aux, (cache, ssm_state)
    return x, aux, cache


def _kv_to_cache(cfg: ModelConfig, kind, kv, policy, batch, capacity, dtype):
    """Convert (k, v) from prefill attention into a filled layer cache."""
    if kind == "rwkv":
        return kv  # already an RWKVState
    if cfg.ssm and cfg.hybrid_parallel:
        (k, v), ssm_state = kv
    else:
        k, v = kv
    ccfg = cache_cfg_for(cfg, kind, policy, batch, capacity)
    c = cache_lib.init_layer_cache(ccfg, dtype)
    c = cache_lib.prefill_layer_cache(ccfg, c, k, v)
    if cfg.ssm and cfg.hybrid_parallel:
        return (c, ssm_state)
    return c


# ---------------------------------------------------------------------------
# Full forward passes


def forward(cfg: ModelConfig, params, batch: dict, mode: str = "train",
            policy: CompressionPolicy | None = None, capacity: int = 0,
            remat: bool = False, remat_policy: str = "full",
            q_chunk_target: int = 512, cache_dtype=jnp.bfloat16,
            unroll_layers: bool = False, prefill_mode: str = "monolithic",
            fused: str = "auto", start_pos: int = 0, init_caches=None,
            padded_tail: bool = False, true_len=None):
    """Full-sequence forward.

    mode="train": returns (logits, aux_loss)
    mode="prefill": returns (logits_last [B, 1, vocab...], caches, aux)

    ``start_pos`` > 0 is the **suffix-offset prefill entry** (prefix
    cache): ``batch`` holds only the tokens after a chunk-aligned cached
    prefix, ``init_caches`` is the cache tree with the prefix chunks
    already spliced in, positions are offset by ``start_pos``, and every
    layer runs the streaming pipeline over the suffix with the cached
    chunks visible as compressed history.  Requires
    ``prefill_mode="streaming"`` and a model whose every layer supports it.

    ``padded_tail`` / ``true_len`` are the length-bucketing hooks (same
    streaming-only requirement): the batch is right-padded to a chunk
    multiple, the last chunk-width block stays out of compression (it lands
    in the FP16 streaming buffer), cache lengths are set from the traced
    ``true_len``, and the prefill logits come from position ``true_len - 1``
    instead of the last row.

    ``prefill_mode`` selects the prefill pipeline: "monolithic" (full-seq
    attention, then one batched compression event per layer) or "streaming"
    (chunked compress-as-you-go — the FP16 K/V history is never
    materialized; unsupported layers fall back per
    :func:`repro.models.attention.streaming_prefill_supported`).  ``fused``
    picks the kernel path for prefill ("auto" = Pallas on TPU / oracles
    elsewhere, "interpret" forces the kernels, "off" = portable XLA) —
    monolithic prefill routes full-sequence attention through the
    ``flash_prefill`` kernel under the same knob.

    ``unroll_layers`` fully unrolls the layer-stack scan.  Needed inside
    (partially) manual ``shard_map`` regions, where XLA's SPMD partitioner
    cannot handle while loops (the PowerSGD train step); everywhere else
    the scan keeps compile time O(pattern).
    """
    x = embed_tokens(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.arange(start_pos, start_pos + S, dtype=jnp.int32)
    prefix_len = cfg.num_prefix_tokens if cfg.modality == "vlm" else 0
    q_chunk = pick_q_chunk(S, q_chunk_target)
    want_kv = mode == "prefill"
    if start_pos and not (want_kv and prefill_mode == "streaming"):
        raise ValueError("start_pos > 0 requires prefill_mode='streaming'")
    if padded_tail and not (want_kv and prefill_mode == "streaming"):
        raise ValueError("padded_tail requires prefill_mode='streaming'")
    attn_impl = "chunked"
    if want_kv and fused == "interpret":
        attn_impl = "flash-interpret"
    elif want_kv and fused == "auto" and kernel_ops.on_tpu():
        attn_impl = "flash"

    if want_kv and prefill_mode == "streaming":
        def unit_body_stream(carry, xs):
            unit_params, unit_caches = xs if init_caches is not None else (xs, None)
            x, aux = carry
            caches = []
            for i, kind in enumerate(cfg.layer_pattern):
                x, a, c = _apply_block_prefill(
                    cfg, unit_params[i], x, kind, positions, prefix_len,
                    q_chunk, policy, B, capacity, cache_dtype, fused,
                    attn_impl,
                    cache=None if unit_caches is None else unit_caches[i],
                    start_pos=start_pos, padded_tail=padded_tail,
                    true_len=true_len)
                aux = aux + a
                caches.append(c)
            return (x, aux), tuple(caches)

        scan_xs = (params["blocks"] if init_caches is None
                   else (params["blocks"], init_caches))
        (x, aux), caches = jax.lax.scan(
            unit_body_stream, (x, jnp.zeros((), jnp.float32)), scan_xs,
            unroll=cfg.pattern_repeats if unroll_layers else 1)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        if true_len is not None:
            # Bucketed prefill: the last REAL token of this call's input
            # sits at row true_len - 1 (traced), not at the padded S - 1.
            last = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(true_len, jnp.int32) - 1, 1, axis=1)
        else:
            last = x[:, -1:, :]
        with jax.named_scope("logits"):
            logits = logits_from_hidden(cfg, params, last)
        return logits, tuple(caches), aux

    def unit_body(carry, unit_params):
        x, aux = carry
        kvs = []
        for i, kind in enumerate(cfg.layer_pattern):
            x, a, kv = _apply_block_train(cfg, unit_params[i], x, kind, positions,
                                          prefix_len, q_chunk, want_kv,
                                          attn_impl=attn_impl)
            aux = aux + a
            if want_kv:
                kvs.append(kv)
        return (x, aux), tuple(kvs) if want_kv else None

    if remat and not want_kv:
        ckpt_policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                       if remat_policy == "dots" else None)
        body = jax.checkpoint(unit_body, policy=ckpt_policy)
    else:
        body = unit_body
    (x, aux), kv_stacks = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                       params["blocks"],
                                       unroll=cfg.pattern_repeats if unroll_layers else 1)
    x = apply_norm(x, params["final_norm"], cfg.norm)

    if mode == "train":
        logits = logits_from_hidden(cfg, params, x)
        return logits, aux

    # prefill: convert stacked (k, v) into caches, logits for last position only
    caches = []
    for i, kind in enumerate(cfg.layer_pattern):
        conv = functools.partial(_kv_to_cache, cfg, kind, policy=policy, batch=B,
                                 capacity=capacity, dtype=cache_dtype)
        caches.append(jax.lax.map(conv, kv_stacks[i]))
    logits = logits_from_hidden(cfg, params, x[:, -1:, :])
    return logits, tuple(caches), aux


def decode_tokens(cfg: ModelConfig, params, token_batch: dict, caches,
                  pos, policy: CompressionPolicy, capacity: int,
                  fused: str = "auto", block_tables=None):
    """One decode step.  token_batch: {"tokens": [B, 1(...)]}.

    ``pos`` is a scalar int32 or a per-slot ``[B]`` vector (continuous
    batching: each batch row decodes at its own absolute position and its
    layer caches advance at their own per-slot lengths).  ``fused`` selects
    the GEAR attend path (see :func:`repro.models.attention.attention_decode`).
    ``block_tables [B, C]`` is required when ``caches`` holds paged layers
    (one table addresses every layer's pool); layers that stayed dense in a
    mixed tree ignore it.  Returns (logits [B, 1, ...], new caches)."""
    x = embed_tokens(cfg, params, token_batch)
    B = x.shape[0]

    def unit_body(x, xs):
        unit_params, unit_caches = xs
        new_caches = []
        for i, kind in enumerate(cfg.layer_pattern):
            x, nc = _apply_block_decode(cfg, unit_params[i], x, kind, pos,
                                        unit_caches[i], policy, B, capacity,
                                        fused=fused,
                                        block_tables=block_tables)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(unit_body, x, (params["blocks"], caches))
    with jax.named_scope("logits"):
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = logits_from_hidden(cfg, params, x)
    return logits, new_caches
