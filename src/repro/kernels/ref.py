"""Pure-jnp oracles for every Pallas kernel (same contracts, no tiling).

These are the correctness ground truth for the kernel tests and the
portable fallback used on CPU/GPU backends.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import outlier as ol
from repro.core import packing
from repro.core import quant as q_lib

__all__ = ["quant_pack_ref", "gear_decode_ref", "gear_decode_paged_ref",
           "gear_hist_block_ref", "flash_prefill_ref", "gear_compress_ref",
           "flash_block_ref", "gather_paged_operands"]

NEG_INF = -1e30


def quant_pack_ref(x: jnp.ndarray, bits: int):
    """Per-column (channel) asymmetric quantize + pack.

    x: [N, n, d] -> (packed int32 [N, n, d*bits/32], scale [N, d], zero [N, d]).
    Groups are whole columns (rows reduced) — the KCVT/chunked layout.
    """
    xf = x.astype(jnp.float32)
    mn = jnp.min(xf, axis=1)
    mx = jnp.max(xf, axis=1)
    scale = jnp.maximum((mx - mn) / (2**bits - 1), 1e-8)
    codes = jnp.clip(jnp.round((xf - mn[:, None, :]) / scale[:, None, :]),
                     0, 2**bits - 1).astype(jnp.int32)
    return packing.pack(codes, bits), scale, mn


def _dequant(packed, scale_full, zero_full, bits, d):
    codes = packing.unpack(packed, bits, d).astype(jnp.float32)
    return codes * scale_full + zero_full


def gear_decode_ref(
    q: jnp.ndarray,          # [BH, G, Dh]
    k_packed: jnp.ndarray,   # [BH, S, L] int32
    k_scale: jnp.ndarray,    # [BH, C, Dh]
    k_zero: jnp.ndarray,
    v_packed: jnp.ndarray,   # [BH, S, L]
    v_scale: jnp.ndarray,    # [BH, S, Gv]
    v_zero: jnp.ndarray,
    n_comp: jnp.ndarray,     # [] or [BH] int32 — valid compressed tokens
    *,
    bits: int,
    chunk: int,
    scale_factor: float,
    k_a=None, k_b=None,      # [BH, S, r] / [BH, C, Dh, r]
    v_a=None, v_b=None,
    k_sp_val=None, k_sp_idx=None,   # [BH, C, Dh, Ks]
    v_sp_val=None, v_sp_idx=None,   # [BH, S, Kv]
):
    """Unnormalized online-softmax decode attention over a GEAR cache.

    ``n_comp`` may be a scalar (uniform extent) or a per-row ``[BH]`` vector
    (ragged continuous batches): positions past each row's own extent get
    no weight, so every output row depends only on its own slot's cache.
    A row at extent 0 returns ``(0, NEG_INF, 0)``, the kernel's init triple
    (it works on no chunk); the caller's buffer merge gives it zero weight.
    Returns (acc [BH, G, Dh] f32 exp-weighted V sum, m [BH, G] score max,
    l [BH, G] sum of exp) so the caller can merge the fp16 buffer region.
    """
    BH, S, L = k_packed.shape
    Dh = k_scale.shape[-1]
    C = S // chunk
    f32 = jnp.float32

    sc = jnp.repeat(k_scale.astype(f32), chunk, axis=1)
    zr = jnp.repeat(k_zero.astype(f32), chunk, axis=1)
    k_hat = _dequant(k_packed, sc, zr, bits, Dh)                 # [BH, S, Dh]
    if k_sp_val is not None:
        oh = (k_sp_idx[..., None] == jnp.arange(chunk)).astype(f32)  # [BH,C,Dh,Ks,nb]
        k_hat = k_hat + jnp.einsum("xcdk,xcdkn->xcnd", k_sp_val.astype(f32), oh
                                   ).reshape(BH, S, Dh)
    s = jnp.einsum("xgd,xsd->xgs", q.astype(f32), k_hat)
    if k_a is not None:
        qb = jnp.einsum("xgd,xcdr->xgcr", q.astype(f32), k_b.astype(f32))
        a_c = k_a.astype(f32).reshape(BH, C, chunk, -1)
        s = s + jnp.einsum("xgcr,xcnr->xgcn", qb, a_c).reshape(BH, -1, S)
    s = s * scale_factor
    n_comp = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (BH,))
    valid = jnp.arange(S)[None, :] < n_comp[:, None]           # [BH, S]
    s = jnp.where(valid[:, None, :], s, NEG_INF)

    m = jnp.max(s, axis=-1)
    # exp(NEG_INF - m) is already 0 for a row with a live token; the select
    # zeroes an empty row's exp(0) too
    p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)

    gv = v_scale.shape[-1]
    vsc = jnp.repeat(v_scale.astype(f32), Dh // gv, axis=-1)
    vzr = jnp.repeat(v_zero.astype(f32), Dh // gv, axis=-1)
    v_hat = _dequant(v_packed, vsc, vzr, bits, Dh)
    if v_sp_val is not None:
        oh = (v_sp_idx[..., None] == jnp.arange(Dh)).astype(f32)
        v_hat = v_hat + jnp.einsum("xsk,xskd->xsd", v_sp_val.astype(f32), oh)
    acc = jnp.einsum("xgs,xsd->xgd", p, v_hat)
    if v_a is not None:
        pa = jnp.einsum("xgcn,xcnr->xgcr", p.reshape(BH, -1, C, chunk),
                        v_a.astype(f32).reshape(BH, C, chunk, -1))
        acc = acc + jnp.einsum("xgcr,xcdr->xgd", pa, v_b.astype(f32))
    return acc, m, l


def gear_decode_paged_ref(
    q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, n_comp,
    block_tables, *,
    bits: int, chunk: int, scale_factor: float,
    k_a=None, k_b=None, v_a=None, v_b=None,
    k_sp_val=None, k_sp_idx=None, v_sp_val=None, v_sp_idx=None,
):
    """Oracle for :func:`repro.kernels.gear_decode.gear_decode_paged` and
    the portable CPU/GPU paged-decode fallback.

    Takes the *same* operands as the paged kernel — head-flattened pool
    pages ``[P*H, ...one-chunk]`` plus ``block_tables [B, C]`` — gathers
    them back to the dense row layout (page ``bt[b, c]``, head ``h`` →
    row ``bt[b, c]*H + h``), and defers to :func:`gear_decode_ref`.  Under
    the pool's zero-page invariant the gathered operands are bitwise equal
    to the dense cache's, so this oracle is exact, not approximate.
    """
    BH = q.shape[0]
    g = gather_paged_operands(
        block_tables, BH,
        dict(k_packed=k_packed, k_scale=k_scale, k_zero=k_zero,
             v_packed=v_packed, v_scale=v_scale, v_zero=v_zero,
             k_a=k_a, k_b=k_b, v_a=v_a, v_b=v_b,
             k_sp_val=k_sp_val, k_sp_idx=k_sp_idx,
             v_sp_val=v_sp_val, v_sp_idx=v_sp_idx))
    return gear_decode_ref(
        q, g["k_packed"], g["k_scale"], g["k_zero"],
        g["v_packed"], g["v_scale"], g["v_zero"], n_comp,
        bits=bits, chunk=chunk, scale_factor=scale_factor,
        k_a=g["k_a"], k_b=g["k_b"], v_a=g["v_a"], v_b=g["v_b"],
        k_sp_val=g["k_sp_val"], k_sp_idx=g["k_sp_idx"],
        v_sp_val=g["v_sp_val"], v_sp_idx=g["v_sp_idx"])


def gather_paged_operands(block_tables, BH: int, pools: dict) -> dict:
    """Gather head-flattened pool operands ``[P*H, pg0, ...]`` back to the
    dense ``[BH, C*pg0, ...]`` row layout through ``block_tables [B, C]``
    (None leaves pass through).  Shared by the paged oracles and the
    portable paged-history path of ``gear_attend_block``."""
    bt = jnp.asarray(block_tables, jnp.int32)
    B, C = bt.shape
    H = BH // B
    # [B, H, C] flat pool rows, flattened to [BH, C] in bh-major order
    rows = (bt[:, None, :] * H + jnp.arange(H)[None, :, None]).reshape(BH, C)

    def gather(pool):
        if pool is None:
            return None
        g = pool[rows]                               # [BH, C, pg0, ...]
        return g.reshape((BH, C * g.shape[2]) + g.shape[3:])

    return {name: gather(pool) for name, pool in pools.items()}


def flash_prefill_ref(q, k, v, positions, *, causal: bool = True,
                      window: int = 0, prefix_len: int = 0,
                      softcap: float = 0.0):
    """Blocked-attention oracle.  q,k,v: [BH, S, Dh] -> [BH, S, Dh]."""
    f32 = jnp.float32
    Dh = q.shape[-1]
    s = jnp.einsum("xqd,xkd->xqk", q.astype(f32), k.astype(f32)) * Dh**-0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qp, kp = positions, positions
    ok = jnp.ones(s.shape[-2:], bool)
    if causal:
        ok = qp[:, None] >= kp[None, :]
    if window:
        ok = ok & (qp[:, None] - kp[None, :] < window)
    if prefix_len:
        ok = ok | ((qp[:, None] < prefix_len) & (kp[None, :] < prefix_len))
    s = jnp.where(ok[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("xqk,xkd->xqd", w, v.astype(f32)).astype(q.dtype)


def gear_compress_ref(x: jnp.ndarray, *, bits: int, scheme: str,
                      group: int | None = None, n_out: int = 0,
                      stat_dtype: str = "bfloat16"):
    """Oracle for :func:`repro.kernels.gear_compress.gear_compress`.

    Built directly on :mod:`repro.core.quant` / :mod:`repro.core.outlier`,
    so its outputs are bit-identical to the corresponding pieces of
    :func:`repro.core.gear.compress_matrix` — this is both the kernel's
    ground truth and the portable CPU/GPU fallback of the fused compression
    path.  x: [N, nb, d] -> (packed, scale, zero, sp_val, sp_idx, resid);
    sp_* are None when ``n_out == 0``; scale/zero are the *unrounded* f32
    compact stats while ``resid`` is computed against stats rounded through
    ``stat_dtype`` (what the cache stores — what the SVD solver must see).
    """
    per_channel = scheme == "per_channel"
    sp_val = sp_idx = None
    remainder = x
    dense = 0.0
    if n_out:
        sp, remainder = ol.filter_outliers_k(x, n_out, "token" if per_channel
                                             else "channel")
        sp_val, sp_idx = sp.values.astype(jnp.float32), sp.indices
        dense = ol.densify(sp)
    qt = q_lib.quantize(remainder, bits, scheme, group,
                        stat_dtype=jnp.float32)
    qt_r = dataclasses.replace(qt, scale=q_lib.round_to_dtype(qt.scale, stat_dtype),
                               zero=q_lib.round_to_dtype(qt.zero, stat_dtype))
    resid = x.astype(jnp.float32) - q_lib.dequantize(qt_r) - dense
    return qt.packed, qt.scale, qt.zero, sp_val, sp_idx, resid


def flash_block_ref(q, k, v, kv_len, *, scale: float, softcap: float = 0.0):
    """Oracle for :func:`repro.kernels.flash_prefill.flash_prefill_block`.

    q,k,v: [N, T, Dh]; kv_len [N].  Returns unnormalized (acc [N, T, Dh],
    m [N, T], l [N, T]) — the caller merges with a history triple.
    """
    f32 = jnp.float32
    N, T, _ = q.shape
    s = jnp.einsum("ntd,nsd->nts", q.astype(f32), k.astype(f32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(T)[None, :]
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (N,))
    ok = (ki <= qi)[None] & (ki[None] < kv_len[:, None, None])
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("nts,nsd->ntd", p, v.astype(f32))
    return acc, m, l


def gear_hist_block_ref(
    q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, n_comp, *,
    bits: int, chunk: int, scale_factor: float,
    k_a=None, k_b=None, v_a=None, v_b=None,
    k_sp_val=None, k_sp_idx=None, v_sp_val=None, v_sp_idx=None,
):
    """Block-query twin of :func:`gear_decode_ref` tuned for the streaming-
    prefill oracle path: same contract and (f32) math, but the low-rank and
    outlier terms are densified into K̂/V̂ up front — a per-chunk A·Bᵀ GEMM
    and a vals-only scatter — so they ride the two big score/value GEMMs
    instead of paying XLA's small-einsum overhead once per scanned chunk.
    The factored forms stay in ``gear_decode`` where they belong (VMEM
    residency on TPU).  A row at extent 0 returns ``(0, NEG_INF, 0)``, as
    :func:`gear_decode_ref` does.  Returns (acc [BH, G, Dh], m [BH, G],
    l [BH, G]).
    """
    BH, S, L = k_packed.shape
    Dh = k_scale.shape[-1]
    C = S // chunk
    f32 = jnp.float32
    qf = q.astype(f32)

    sc = jnp.repeat(k_scale.astype(f32), chunk, axis=1)
    zr = jnp.repeat(k_zero.astype(f32), chunk, axis=1)
    k_hat = _dequant(k_packed, sc, zr, bits, Dh)                 # [BH, S, Dh]
    if k_a is not None:
        a_c = k_a.astype(f32).reshape(BH, C, chunk, -1)
        k_hat = k_hat + jnp.einsum("xcnr,xcdr->xcnd", a_c,
                                   k_b.astype(f32)).reshape(BH, S, Dh)
    if k_sp_val is not None:
        # densify via a 2k-deep select chain (set semantics, like
        # outlier.densify) — XLA CPU scatters serialize, selects vectorize
        iota_n = jnp.arange(chunk)[None, None, None, :]
        sp = jnp.zeros((BH, C, Dh, chunk), f32)
        for j in range(k_sp_val.shape[-1]):
            sp = jnp.where(iota_n == k_sp_idx[..., j:j + 1],
                           k_sp_val[..., j:j + 1].astype(f32), sp)
        k_hat = k_hat + jnp.swapaxes(sp, 2, 3).reshape(BH, S, Dh)
    s = jnp.einsum("xgd,xsd->xgs", qf, k_hat) * scale_factor
    n_comp = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (BH,))
    valid = jnp.arange(S)[None, :] < n_comp[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)

    gv = v_scale.shape[-1]
    vsc = jnp.repeat(v_scale.astype(f32), Dh // gv, axis=-1)
    vzr = jnp.repeat(v_zero.astype(f32), Dh // gv, axis=-1)
    v_hat = _dequant(v_packed, vsc, vzr, bits, Dh)
    if v_a is not None:
        a_c = v_a.astype(f32).reshape(BH, C, chunk, -1)
        v_hat = v_hat + jnp.einsum("xcnr,xcdr->xcnd", a_c,
                                   v_b.astype(f32)).reshape(BH, S, Dh)
    if v_sp_val is not None:
        iota_d = jnp.arange(Dh)[None, None, :]
        sp_v = jnp.zeros((BH, S, Dh), f32)
        for j in range(v_sp_val.shape[-1]):
            sp_v = jnp.where(iota_d == v_sp_idx[..., j:j + 1],
                             v_sp_val[..., j:j + 1].astype(f32), sp_v)
        v_hat = v_hat + sp_v
    acc = jnp.einsum("xgs,xsd->xgd", p, v_hat)
    return acc, m, l
