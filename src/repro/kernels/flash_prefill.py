"""Pallas TPU kernels: blocked flash attention for prefill.

``flash_prefill`` is the classic FlashAttention-2 schedule on the TPU memory
hierarchy: grid (BH, q_blocks, kv_blocks) with the KV dimension innermost;
running max / sum-exp / accumulator live in VMEM scratch, one [Bq, Dh] tile
is written to HBM per q block.  Supports the mask family the assigned archs
need: causal, sliding window (gemma3 locals), and bidirectional prefix
(paligemma).

``flash_prefill_block`` is the history-aware variant used by streaming
chunked prefill: one causal query-block × in-flight-KV-block tile per grid
step, returning the *unnormalized* (acc, m, l) online-softmax triple so the
caller can merge it with the compressed-history triple from
:func:`repro.kernels.gear_decode.gear_decode` (two-piece online softmax —
the streaming pipeline's step (a), see DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_prefill", "flash_prefill_block"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bk: int, nk: int, scale: float, window: int,
            prefix_len: int, softcap: float):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # [bq, Dh]
    k = k_ref[0].astype(jnp.float32)              # [bk, Dh]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qp = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kp = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = qp >= kp
    if window:
        ok &= qp - kp < window
    if prefix_len:
        ok |= (qp < prefix_len) & (kp < prefix_len)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * corr[:, None] + jnp.sum(p, axis=-1)[:, None]
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(kb == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, 0:1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bq", "bk", "window", "prefix_len", "softcap",
                     "kv_repeat", "interpret"),
)
def flash_prefill(q, k, v, *, bq: int = 128, bk: int = 128, window: int = 0,
                  prefix_len: int = 0, softcap: float = 0.0,
                  kv_repeat: int = 1, interpret: bool = False):
    """q: [BHq, S, Dh]; k,v: [BHq/kv_repeat, S, Dh] -> [BHq, S, Dh].

    Causal attention.  ``kv_repeat`` maps each group of ``kv_repeat``
    consecutive query rows onto one shared K/V row via the BlockSpec index
    map (GQA: rows laid out (B, Hkv, G) query-head-major) — no broadcast
    copy of K/V ever lands in HBM.
    """
    BH, S, Dh = q.shape
    assert BH % kv_repeat == 0 and k.shape[0] == BH // kv_repeat, \
        (BH, kv_repeat, k.shape)
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, scale=Dh**-0.5, window=window,
        prefix_len=prefix_len, softcap=softcap)
    kv_spec = pl.BlockSpec((1, bk, Dh), lambda x, i, j: (x // kv_repeat, j, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda x, i, j: (x, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda x, i, j: (x, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _block_kernel(len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, softcap: float):
    q = q_ref[0].astype(jnp.float32)              # [T, Dh]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    T = q.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    ok = (ki <= qi) & (ki < len_ref[pl.program_id(0)])
    s = jnp.where(ok, s, NEG_INF)
    m = jnp.max(s, axis=-1)                       # [T]
    p = jnp.exp(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    acc_ref[0] = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    m_ref[0] = jnp.broadcast_to(m[:, None], m_ref[0].shape)
    l_ref[0] = jnp.broadcast_to(l[:, None], l_ref[0].shape)


@functools.partial(
    jax.jit, static_argnames=("scale", "softcap", "interpret"))
def flash_prefill_block(q, k, v, kv_len, *, scale: float, softcap: float = 0.0,
                        interpret: bool = False):
    """Causal attention of one in-flight block against itself, unnormalized.

    q, k, v: [N, T, Dh]; kv_len: [N] int32 — query row t of program n sees
    keys j with ``j <= t`` and ``j < kv_len[n]`` (partial tail chunks mask
    their padding).  Returns (acc [N, T, Dh] f32, m [N, T, 128], l
    [N, T, 128]) in the same unnormalized convention as ``gear_decode`` so
    the two triples merge with one softmax rescale.  Oracle:
    :func:`repro.kernels.ref.flash_block_ref`.
    """
    N, T, Dh = q.shape
    f32 = jnp.float32
    kernel = functools.partial(_block_kernel, scale=scale, softcap=softcap)
    n = lambda i, lens: (i, 0, 0)
    # kv_len rides scalar prefetch (SMEM): one length per grid program
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, T, Dh), n),
            pl.BlockSpec((1, T, Dh), n),
            pl.BlockSpec((1, T, Dh), n),
        ],
        out_specs=(
            pl.BlockSpec((1, T, Dh), n),
            pl.BlockSpec((1, T, 128), n),
            pl.BlockSpec((1, T, 128), n),
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((N, T, Dh), f32),
            jax.ShapeDtypeStruct((N, T, 128), f32),
            jax.ShapeDtypeStruct((N, T, 128), f32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (N,)), q, k, v)
