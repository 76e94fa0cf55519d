"""Jit'd wrappers dispatching Pallas kernels (TPU) or jnp oracles (CPU/GPU).

``gear_attend`` is the drop-in high-performance replacement for
:func:`repro.core.cache.attend`: the compressed region goes through the
fused ``gear_decode`` kernel (or its oracle off-TPU), the FP16 streaming
buffer is merged with one softmax-rescale, matching the paper's streaming
design where only compressed history pays the dequantization path.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cache import (CacheConfig, PagedGEARLayerCache, layer_pool,
                              page_layout, streaming_supported)
from repro.launch.mesh import MODEL, dp_axes
from repro.kernels import ref as ref_ops
from repro.kernels.flash_prefill import flash_prefill, flash_prefill_block
from repro.kernels.gear_compress import gear_compress
from repro.kernels.gear_decode import gear_decode, gear_decode_paged
from repro.kernels.quant_pack import quant_pack

__all__ = ["on_tpu", "fused_supported", "shard_over_heads",
           "gear_attend", "gear_attend_paged", "gear_attend_block",
           "gear_compress_chunks", "flash_attention", "quantize_chunk"]

NEG_INF = -1e30


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def shard_over_heads(fn, args, *, head_axis: int, n_heads: int):
    """Run ``fn(*args)`` once per head shard of the context mesh.

    A ``pallas_call`` is opaque to the SPMD partitioner, so a kernel traced
    under a mesh (:meth:`repro.serving.Engine.mesh_context`) is wrapped in
    ``shard_map``: every array's leading (slot) dim splits over the data
    axes and its ``head_axis`` dim over ``model`` — where they divide, the
    rule :func:`repro.dist.sharding.cache_pspecs` lays the cache out by —
    and ``fn`` runs on one shard's slots and heads.  The output is one
    array in the first argument's layout.  Without a context mesh, ``fn``
    simply runs on the whole arrays.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn(*args)
    dp = dp_axes(mesh)
    batch = jax.tree.leaves(args)[0].shape[0]
    b_ax = dp if dp and batch % math.prod(mesh.shape[a] for a in dp) == 0 else None
    h_ax = MODEL if MODEL in mesh.shape and n_heads % mesh.shape[MODEL] == 0 else None

    def spec(x):
        ent = [b_ax] + [None] * (x.ndim - 1)
        if x.ndim > head_axis:
            ent[head_axis] = h_ax
        return P(*ent)

    return jax.shard_map(fn, mesh=mesh, in_specs=jax.tree.map(spec, args),
                         out_specs=spec(jax.tree.leaves(args)[0]),
                         check_vma=False)(*args)


def _flat(x, bh):
    return None if x is None else x.reshape((bh,) + x.shape[2:])


def fused_supported(cfg: CacheConfig) -> bool:
    """True when this layer cache has the fused-kernel layout.

    The kernel streams one K-stat row per chunk, so it needs a GEAR cache
    with per-channel K quantization at chunk granularity (group == chunk);
    both recommended policies (GEAR-KCVT-4bit, GEAR-KIVI-2bit) qualify, the
    FlexGen-style per-token-group backbone (K in the V layout) does not.
    The check is static — safe to branch on at trace time.  Streaming
    prefill's history scorer shares the layout, so this is the same
    predicate as :func:`repro.core.cache.streaming_supported`.
    """
    return streaming_supported(cfg)


def gear_compress_chunks(x: jnp.ndarray, *, bits: int, scheme: str,
                         group: int | None, n_out: int,
                         stat_dtype: str = "bfloat16",
                         force_kernel: bool = False, interpret: bool = False):
    """Fused chunk compression: Pallas kernel on TPU (or forced interpret),
    bit-exact jnp oracle elsewhere.  x: [N, nb, d] — see
    :func:`repro.kernels.ref.gear_compress_ref` for the contract."""
    if on_tpu() or force_kernel:
        return gear_compress(x, bits=bits, scheme=scheme, group=group,
                             n_out=n_out, stat_dtype=stat_dtype,
                             interpret=interpret or not on_tpu())
    return ref_ops.gear_compress_ref(x, bits=bits, scheme=scheme, group=group,
                                     n_out=n_out, stat_dtype=stat_dtype)


def _gear_operands(cfg: CacheConfig, cache, BH: int):
    """Flatten a GEAR layer cache into the [BH]-leading operand groups the
    ``gear_decode`` kernel/oracle contract takes — shared by the decode
    step (:func:`gear_attend`) and the streaming prefill block
    (:func:`gear_attend_block`), so a new cache leaf is threaded once."""
    pol = cfg.policy
    lr = dict(
        k_a=_flat(cache.k_a, BH), k_b=_flat(cache.k_b, BH),
        v_a=_flat(cache.v_a, BH), v_b=_flat(cache.v_b, BH),
    ) if pol.use_lowrank else {}
    sp = dict(
        k_sp_val=_flat(cache.k_sp_val, BH), k_sp_idx=_flat(cache.k_sp_idx, BH),
        v_sp_val=_flat(cache.v_sp_val, BH), v_sp_idx=_flat(cache.v_sp_idx, BH),
    ) if pol.use_sparse else {}
    arrays = (_flat(cache.k_packed, BH), _flat(cache.k_scale, BH),
              _flat(cache.k_zero, BH), _flat(cache.v_packed, BH),
              _flat(cache.v_scale, BH), _flat(cache.v_zero, BH))
    return arrays, lr, sp


def _merge_buffer(cfg: CacheConfig, cache, qf, acc, m, l, n_buf, scale):
    """Merge the FP16 streaming-buffer region into a history (acc, m, l)
    triple and normalize — the XLA tail both decode paths (dense
    :func:`gear_attend`, paged :func:`gear_attend_paged`) share, so the
    merge math is one piece of code and stays bit-identical across
    layouts.  qf: [BH, G, Dh] f32; returns normalized [BH, G, Dh] f32."""
    BH = qf.shape[0]
    nb = cfg.chunk
    s_buf = jnp.einsum("xgd,xnd->xgn", qf,
                       _flat(cache.buf_k, BH).astype(jnp.float32)) * scale
    buf_valid = jnp.arange(nb)[None, None, :] < n_buf[:, None, None]
    s_buf = jnp.where(buf_valid, s_buf, NEG_INF)
    m_buf = jnp.max(s_buf, axis=-1)
    m_tot = jnp.maximum(m, m_buf)
    p_buf = jnp.exp(s_buf - m_tot[..., None])
    acc_buf = jnp.einsum("xgn,xnd->xgd", p_buf,
                         _flat(cache.buf_v, BH).astype(jnp.float32))
    corr = jnp.exp(m - m_tot)
    l_tot = l * corr + jnp.sum(p_buf, axis=-1)
    return (acc * corr[..., None] + acc_buf) / jnp.maximum(
        l_tot[..., None], 1e-30)


def gear_attend_block(cfg: CacheConfig, cache, q: jnp.ndarray,
                      k_blk: jnp.ndarray, v_blk: jnp.ndarray,
                      n_comp, blk_len, scale: float,
                      force_kernel: bool = False,
                      interpret: bool = False,
                      force_oracle: bool = False) -> jnp.ndarray:
    """Streaming-prefill attention of one query block: compressed history
    + in-flight FP16 block, merged with a two-piece online softmax.

    q: [B, Hq, T, Dh] (the current chunk's queries); k_blk/v_blk:
    [B, H, T, Dh] (the same chunk's uncompressed K/V); ``n_comp`` — scalar
    compressed extent (tokens in chunks already closed, i.e. ``c · n_b``);
    ``blk_len`` — valid tokens in the block (< T only for the tail).
    History scores run the ``gear_decode`` machinery (kernel on TPU, oracle
    elsewhere; ``force_oracle`` pins the jnp oracles even on TPU — the
    ``fused="off"`` escape hatch) with the chunk's T·G query rows sharing
    one extent mask; the block piece is ``flash_prefill_block`` with causal
    masking.  Returns [B, Hq, T, Dh] in q's dtype.
    """
    pol = cfg.policy
    B, Hq, T, Dh = q.shape
    H = cfg.kv_heads
    G = Hq // H
    BH = B * H
    nb = cfg.chunk
    f32 = jnp.float32
    qf = q.astype(f32).reshape(B, H, G, T, Dh)
    use_kernel = (on_tpu() or force_kernel) and not force_oracle
    run_interp = interpret or not on_tpu()

    # --- compressed history: unnormalized (acc, m, l) over T·G query rows --
    kwargs = dict(bits=pol.bits, chunk=nb, scale_factor=scale)
    arrays, lr, sp = _gear_operands(cfg, cache, BH)
    n_comp_bh = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (BH,))
    q_rows = qf.reshape(BH, G * T, Dh)
    common = (q_rows, *arrays, n_comp_bh)
    if use_kernel:
        acc_h, m_h, l_h = gear_decode(*common, interpret=run_interp,
                                      **kwargs, **lr, **sp)
        m_h, l_h = m_h[..., 0], l_h[..., 0]
    else:
        acc_h, m_h, l_h = ref_ops.gear_hist_block_ref(*common, **kwargs,
                                                      **lr, **sp)
    acc_h = acc_h.reshape(B, H, G, T, Dh)
    m_h = m_h.reshape(B, H, G, T)
    l_h = l_h.reshape(B, H, G, T)

    # --- in-flight FP16 block: causal within the chunk ---------------------
    N2 = BH * G
    q_blk = qf.reshape(N2, T, Dh)
    k3 = jnp.broadcast_to(k_blk.astype(f32)[:, :, None], (B, H, G, T, Dh))
    v3 = jnp.broadcast_to(v_blk.astype(f32)[:, :, None], (B, H, G, T, Dh))
    kv_len = jnp.broadcast_to(jnp.asarray(blk_len, jnp.int32), (N2,))
    if use_kernel:
        acc_b, m_b, l_b = flash_prefill_block(
            q_blk, k3.reshape(N2, T, Dh), v3.reshape(N2, T, Dh), kv_len,
            scale=scale, interpret=run_interp)
        m_b, l_b = m_b[..., 0], l_b[..., 0]
    else:
        acc_b, m_b, l_b = ref_ops.flash_block_ref(
            q_blk, k3.reshape(N2, T, Dh), v3.reshape(N2, T, Dh), kv_len,
            scale=scale)
    acc_b = acc_b.reshape(B, H, G, T, Dh)
    m_b = m_b.reshape(B, H, G, T)
    l_b = l_b.reshape(B, H, G, T)

    # --- two-piece merge + normalize ---------------------------------------
    m_tot = jnp.maximum(m_h, m_b)
    c_h = jnp.exp(m_h - m_tot)
    c_b = jnp.exp(m_b - m_tot)
    l_tot = l_h * c_h + l_b * c_b
    out = (acc_h * c_h[..., None] + acc_b * c_b[..., None]) / jnp.maximum(
        l_tot[..., None], 1e-30)
    return out.reshape(B, Hq, T, Dh).astype(q.dtype)


def gear_attend(cfg: CacheConfig, cache, q: jnp.ndarray, scale: float,
                force_kernel: bool = False, interpret: bool = False) -> jnp.ndarray:
    """Decode attention over a GEAR layer cache via the fused kernel path.

    q: [B, Hq, Dh] -> [B, Hq, Dh].  Requires the engine layout
    (group == chunk for K — :func:`fused_supported`; see DESIGN.md) which
    both recommended policies (GEAR-KCVT-4bit, GEAR-KIVI-2bit) satisfy.

    Ragged-aware: ``cache.length`` is the per-slot ``[B]`` length vector and
    every slot attends over exactly its own compressed extent and buffer
    fill, inside the kernel — mixed-length continuous batches take this
    path directly (DESIGN.md §ragged fused decode).  Under a mesh the
    kernel runs per head shard (:func:`shard_over_heads`).
    """
    if not (on_tpu() or force_kernel):
        return _gear_attend(cfg, cache, q, scale, False, False)
    G = q.shape[1] // cfg.kv_heads

    def local(qq, c):
        lcfg = dataclasses.replace(cfg, batch=qq.shape[0],
                                   kv_heads=qq.shape[1] // G)
        return _gear_attend(lcfg, c, qq, scale, True, interpret or not on_tpu())

    return shard_over_heads(local, (q, cache), head_axis=1,
                            n_heads=cfg.kv_heads)


def _gear_attend(cfg: CacheConfig, cache, q, scale: float, kernel: bool,
                 interpret: bool):
    pol = cfg.policy
    B, Hq, Dh = q.shape
    H = cfg.kv_heads
    G = Hq // H
    BH = B * H
    qf = q.astype(jnp.float32).reshape(BH, G, Dh)
    nb = cfg.chunk
    # per-slot extents, repeated per head to match the [B*H] kernel rows
    length = jnp.broadcast_to(jnp.asarray(cache.length, jnp.int32), (B,))
    len_bh = jnp.repeat(length, H)            # [BH]
    n_comp = (len_bh // nb) * nb              # [BH] compressed extent per row
    n_buf = len_bh - n_comp                   # [BH] streaming-buffer fill

    kwargs = dict(bits=pol.bits, chunk=nb, scale_factor=scale)
    arrays, lr, sp = _gear_operands(cfg, cache, BH)
    common = (qf, *arrays, n_comp)
    if kernel:
        acc, m, l = gear_decode(*common, interpret=interpret,
                                **kwargs, **lr, **sp)
        m, l = m[..., 0], l[..., 0]
    else:
        acc, m, l = ref_ops.gear_decode_ref(*common, **kwargs, **lr, **sp)

    # merge the fp16 buffer region (n_b tokens, plain XLA, per-slot masks)
    out = _merge_buffer(cfg, cache, qf, acc, m, l, n_buf, scale)
    return out.reshape(B, Hq, Dh).astype(q.dtype)


def gear_attend_paged(cfg: CacheConfig, pcache: PagedGEARLayerCache,
                      block_tables: jnp.ndarray, q: jnp.ndarray,
                      scale: float, force_kernel: bool = False,
                      interpret: bool = False) -> jnp.ndarray:
    """Paged twin of :func:`gear_attend`: decode attention whose compressed
    history lives in pool pages addressed through ``block_tables [B, C]``.

    The fused path is :func:`gear_decode_paged` (scalar-prefetched tables,
    page gather in the DMA engine, a slot's heads in one grid step); off-TPU
    the :func:`~repro.kernels.ref.gear_decode_paged_ref` oracle converts the
    pages back and defers to the dense oracle.  The FP16 streaming buffer is
    per-slot (not paged) and merges through the same
    :func:`_merge_buffer` tail as the dense path, so a paged slot's output
    is bit-identical to the dense slot's for the same history.
    """
    B, Hq, Dh = q.shape
    H = cfg.kv_heads
    G = Hq // H
    qf = q.astype(jnp.float32).reshape(B * H, G, Dh)
    nb = cfg.chunk
    length = jnp.broadcast_to(jnp.asarray(pcache.length, jnp.int32), (B,))
    n_comp = (length // nb) * nb              # [B] compressed extent per slot
    n_buf = jnp.repeat(length - n_comp, H)    # [BH] streaming-buffer fill
    layout = page_layout(cfg, pcache.side.dtype)
    bt = jnp.asarray(block_tables, jnp.int32)
    if on_tpu() or force_kernel:
        # a stacked pool goes to the kernel whole: its DMAs index the layer
        layer = 0 if pcache.layer is None else pcache.layer
        acc, m, l = gear_decode_paged(
            qf, pcache.codes, pcache.side, n_comp, bt, layer, layout=layout,
            scale_factor=scale, interpret=interpret or not on_tpu())
        m, l = m[..., 0], l[..., 0]
    else:
        pool = layer_pool(pcache)
        acc, m, l = ref_ops.gear_decode_paged_ref(
            qf, pool["codes"], pool["side"], n_comp, bt, layout=layout,
            scale_factor=scale)

    out = _merge_buffer(cfg, pcache, qf, acc, m, l, n_buf, scale)
    return out.reshape(B, Hq, Dh).astype(q.dtype)


def _block_divisor(s: int, target: int) -> int:
    """Largest block size <= target dividing s (flash kernel tiling)."""
    c = min(target, s)
    while s % c:
        c //= 2
    return max(c, 1)


def flash_attention(q, k, v, *, window: int = 0, prefix_len: int = 0,
                    softcap: float = 0.0, kv_repeat: int = 1,
                    interpret: bool = False, bq: int = 128, bk: int = 128):
    """q: [BHq, S, Dh], k/v: [BHq/kv_repeat, S, Dh] causal attention;
    kernel on TPU, oracle elsewhere.

    ``kv_repeat`` > 1 is GQA: the kernel indexes each query head group onto
    its shared K/V row (no broadcast copy).  Block sizes are snapped down
    to divisors of S, so any (padded prompt) length the engine produces is
    legal.
    """
    S = q.shape[1]
    if on_tpu() or interpret:
        return flash_prefill(q, k, v, bq=_block_divisor(S, bq),
                             bk=_block_divisor(S, bk), window=window,
                             prefix_len=prefix_len, softcap=softcap,
                             kv_repeat=kv_repeat,
                             interpret=interpret and not on_tpu())
    if kv_repeat > 1:            # CPU oracle path: plain repeat is fine
        k = jnp.repeat(k, kv_repeat, axis=0)
        v = jnp.repeat(v, kv_repeat, axis=0)
    return ref_ops.flash_prefill_ref(q, k, v, jnp.arange(S), causal=True,
                                     window=window, prefix_len=prefix_len,
                                     softcap=softcap)


def quantize_chunk(x: jnp.ndarray, bits: int, interpret: bool = False):
    """Fused per-channel quantize+pack of a chunk batch [N, n, d]."""
    if on_tpu() or interpret:
        return quant_pack(x, bits, interpret=interpret or not on_tpu())
    return ref_ops.quant_pack_ref(x, bits)
