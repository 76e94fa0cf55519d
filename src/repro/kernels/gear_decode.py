"""Pallas TPU kernel: fused GEAR decode attention.

The TPU-native analogue of the paper's fused CUDA dequant+GEMM: one decode
step attends over the compressed cache without ever materializing the FP16
K/V in HBM.  Per grid step (bh, c) the kernel:

  1. streams one chunk's packed K codes (int32 lanes) into VMEM, unpacks
     with vectorized shift/mask, applies per-channel scale/zero,
  2. densifies the chunk's sparse outliers (iota-compare scatter — 2·k
     vector ops, no gather hardware needed),
  3. adds the low-rank score path factored as (q·B_c)·A_cᵀ — the paper's
     separate-path trick, two rank-r matmuls instead of an [nb, Dh] add,
  4. runs online-softmax accumulation in VMEM scratch across chunks, with
     the V side dequantized/densified the same way.

Outputs are the *unnormalized* (acc, m, l) triple so the caller merges the
FP16 streaming-buffer region (computed in plain XLA — it is n_b tokens) and
normalizes once.  HBM traffic per step ≈ packed bits + stats + factors
≈ (bits/16 + overheads) × the FP16 cache — the memory-roofline win that
produces the paper's throughput gain on memory-bound decode.

Grid: (BH, C).  Block shapes are MXU/VPU aligned: Dh ∈ {64, 128, 256} maps
to lane-dim 128 tiles; the chunk dim (n_b = 64/128) is the sublane dim.

**Ragged batches.**  ``n_comp`` may be a scalar (all slots at one extent) or
a per-row ``[BH]`` vector: each (bh, c) grid program reads its own row's
compressed extent, so mixed-length continuous batches run the fused path
directly.  A row does work only on its ``ceil(n_comp / chunk)`` live chunks:
past them a grid step runs no body, and scores past the extent inside the
last live chunk are masked, so the bytes of dead chunks never reach the math
and may hold anything.  The index maps stay plain: the pipeline may still
fetch a dead chunk's blocks (not where the index repeats, as the paged
layout's dead table entries, all page 0, do); on a v5e that costs less than
clamping the index maps on every grid step.  A row at extent 0 keeps its
init triple ``(0, NEG_INF, 0)``: the caller's ``exp(m - m_tot)`` correction
zeroes it when the row's buffer holds tokens, and a fully empty row (length
0) merges to the mean of its all-masked buffer rows, zeros because
``reset_slot`` zeroes them, exactly matching the oracle.  Either way the math
is per-row only (no cross-slot leakage, no NaN).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gear_decode", "gear_decode_paged"]

NEG_INF = -1e30

# rows (slot-heads) are independent; the chunk axis carries the
# online-softmax accumulator, so only rows may be split across cores
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# K stats ([BH, C, Dh], bf16) are read through a window of this many chunk
# rows: Mosaic cannot index one bf16 sublane at a traced offset, so the
# BlockSpec DMAs an aligned window (re-fetched once per window, not per
# chunk) and the kernel selects the chunk's row from it.
STAT_WINDOW = 16


def _unpack(packed, bits: int, d: int):
    """packed [n, d//per] int32 -> codes f32 [n, d].

    int32 throughout (Mosaic has no unsigned<->float casts); the arithmetic
    shift's sign extension only reaches bits the mask clears."""
    per = 32 // bits
    n = packed.shape[0]
    shifts = (jnp.arange(per, dtype=jnp.int32) * bits)[None, None, :]
    codes = (packed[:, :, None] >> shifts) & (2**bits - 1)
    return codes.reshape(n, d).astype(jnp.float32)


def _window_row(ref, c):
    """Row ``c % rows`` of a [1, rows, Dh] stat window, as f32 [1, Dh]."""
    w = ref[0].astype(jnp.float32)
    rows = w.shape[0]
    if rows == 1:
        return w
    sel = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) == c % rows
    return jnp.sum(jnp.where(sel, w, 0.0), axis=0, keepdims=True)


def _kernel(n_comp_ref, q_ref, kp_ref, ks_ref, kz_ref, vp_ref, vs_ref, vz_ref,
            ka_ref, kb_ref, va_ref, vb_ref,
            ksv_ref, ksi_ref, vsv_ref, vsi_ref,
            acc_ref, m_ref, l_ref,
            *, bits: int, chunk: int, scale_factor: float,
            use_lr: bool, use_sp: bool):
    bh = pl.program_id(0)
    c = pl.program_id(1)
    n = n_comp_ref[bh]

    @pl.when(c == 0)
    def _init():
        acc_ref[0] = jnp.zeros_like(acc_ref[0])
        m_ref[0] = jnp.full_like(m_ref[0], NEG_INF)
        l_ref[0] = jnp.zeros_like(l_ref[0])

    # live chunks are the first ceil(n / chunk); a chunk past them would
    # add corr = 1, p = 0, so its step runs no body
    @pl.when(c * chunk < n)
    def _chunk():
        nb = chunk
        q = q_ref[0].astype(jnp.float32)                       # [G, Dh]
        G, Dh = q.shape

        # ---- K chunk: dequant + outliers ----------------------------------
        k_tile = _unpack(kp_ref[0], bits, Dh)                  # [nb, Dh]
        k_tile = k_tile * _window_row(ks_ref, c) + _window_row(kz_ref, c)
        if use_sp:
            # [Dh, Ks] -> [Ks, Dh]: channel on lanes, like the tile
            ksv = ksv_ref[0, 0].astype(jnp.float32).T
            ksi = ksi_ref[0, 0].T
            row = jax.lax.broadcasted_iota(jnp.int32, (nb, Dh), 0)
            for j in range(ksv.shape[0]):
                k_tile += jnp.where(row == ksi[j:j + 1], ksv[j:j + 1], 0.0)

        s = jax.lax.dot_general(q, k_tile, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, nb]
        if use_lr:
            kb = kb_ref[0, 0].astype(jnp.float32)              # [Dh, r]
            ka = ka_ref[0].astype(jnp.float32)                 # [nb, r]
            qb = jax.lax.dot_general(q, kb, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [G, r]
            s += jax.lax.dot_general(qb, ka, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        s = s * scale_factor

        tok = c * nb + jax.lax.broadcasted_iota(jnp.int32, (G, nb), 1)
        s = jnp.where(tok < n, s, NEG_INF)

        # ---- V chunk --------------------------------------------------------
        v_tile = _unpack(vp_ref[0], bits, Dh)
        gv = vs_ref.shape[-1]
        vsc = jnp.repeat(vs_ref[0].astype(jnp.float32), Dh // gv, axis=-1)
        vzr = jnp.repeat(vz_ref[0].astype(jnp.float32), Dh // gv, axis=-1)
        v_tile = v_tile * vsc + vzr
        if use_sp:
            vsv = vsv_ref[0].astype(jnp.float32)               # [nb, Kv]
            vsi = vsi_ref[0]
            col = jax.lax.broadcasted_iota(jnp.int32, (nb, Dh), 1)
            for j in range(vsv.shape[-1]):
                v_tile += jnp.where(col == vsi[:, j][:, None],
                                    vsv[:, j][:, None], 0.0)

        # ---- online softmax -------------------------------------------------
        m_prev = m_ref[0][:, 0]                                # [G]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                        # [G, nb]
        l_ref[0] = l_ref[0] * corr[:, None] + jnp.sum(p, axis=-1)[:, None]
        pv = jax.lax.dot_general(p, v_tile, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if use_lr:
            va = va_ref[0].astype(jnp.float32)                 # [nb, r]
            vb = vb_ref[0, 0].astype(jnp.float32)              # [Dh, r]
            pa = jax.lax.dot_general(p, va, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [G, r]
            pv += jax.lax.dot_general(pa, vb, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        acc_ref[0] = acc_ref[0] * corr[:, None] + pv
        m_ref[0] = jnp.broadcast_to(m_new[:, None], m_ref[0].shape)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "chunk", "scale_factor", "interpret"),
)
def gear_decode(
    q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, n_comp,
    k_a=None, k_b=None, v_a=None, v_b=None,
    k_sp_val=None, k_sp_idx=None, v_sp_val=None, v_sp_idx=None,
    *, bits: int, chunk: int, scale_factor: float, interpret: bool = False,
):
    """See ref.gear_decode_ref for the contract.  Returns (acc, m, l).

    ``n_comp``: scalar or per-row [BH] int32 compressed extents (ragged).
    """
    BH, G, Dh = q.shape
    S = k_packed.shape[1]
    C = S // chunk
    Lp = k_packed.shape[-1]
    use_lr = k_a is not None
    use_sp = k_sp_val is not None
    r = k_a.shape[-1] if use_lr else 1
    ks2 = k_sp_val.shape[-1] if use_sp else 1
    kv2 = v_sp_val.shape[-1] if use_sp else 1
    gv = v_scale.shape[-1]
    f32 = jnp.float32

    # dummy placeholders keep the kernel signature static
    if not use_lr:
        k_a = jnp.zeros((BH, S, 1), f32); k_b = jnp.zeros((BH, C, Dh, 1), f32)
        v_a = jnp.zeros((BH, S, 1), f32); v_b = jnp.zeros((BH, C, Dh, 1), f32)
    if not use_sp:
        k_sp_val = jnp.zeros((BH, C, Dh, 1), f32)
        k_sp_idx = jnp.full((BH, C, Dh, 1), -1, jnp.int32)
        v_sp_val = jnp.zeros((BH, S, 1), f32)
        v_sp_idx = jnp.full((BH, S, 1), -1, jnp.int32)

    # scalar extents broadcast to one row per (batch, head) grid program;
    # they ride scalar prefetch (SMEM) so each program reads its own row
    n_comp_arr = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (BH,))
    sw = min(C, STAT_WINDOW)

    kernel = functools.partial(
        _kernel, bits=bits, chunk=chunk, scale_factor=scale_factor,
        use_lr=use_lr, use_sp=use_sp)
    bh = lambda x, c, n: (x, 0, 0)
    tile = lambda x, c, n: (x, c, 0)
    tile4 = lambda x, c, n: (x, c, 0, 0)
    stat = lambda x, c, n: (x, c // sw, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, C),
        in_specs=[
            pl.BlockSpec((1, G, Dh), bh),                  # q
            pl.BlockSpec((1, chunk, Lp), tile),            # k_packed
            pl.BlockSpec((1, sw, Dh), stat),               # k_scale
            pl.BlockSpec((1, sw, Dh), stat),               # k_zero
            pl.BlockSpec((1, chunk, Lp), tile),            # v_packed
            pl.BlockSpec((1, chunk, gv), tile),            # v_scale
            pl.BlockSpec((1, chunk, gv), tile),            # v_zero
            pl.BlockSpec((1, chunk, r), tile),             # k_a
            pl.BlockSpec((1, 1, Dh, r), tile4),            # k_b
            pl.BlockSpec((1, chunk, r), tile),             # v_a
            pl.BlockSpec((1, 1, Dh, r), tile4),            # v_b
            pl.BlockSpec((1, 1, Dh, ks2), tile4),          # k_sp_val
            pl.BlockSpec((1, 1, Dh, ks2), tile4),          # k_sp_idx
            pl.BlockSpec((1, chunk, kv2), tile),           # v_sp_val
            pl.BlockSpec((1, chunk, kv2), tile),           # v_sp_idx
        ],
        out_specs=(
            pl.BlockSpec((1, G, Dh), bh),
            pl.BlockSpec((1, G, 128), bh),
            pl.BlockSpec((1, G, 128), bh),
        ),
    )
    out_shape = (
        jax.ShapeDtypeStruct((BH, G, Dh), f32),
        jax.ShapeDtypeStruct((BH, G, 128), f32),
        jax.ShapeDtypeStruct((BH, G, 128), f32),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gear_decode",
    )(n_comp_arr, q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero,
      k_a, k_b, v_a, v_b, k_sp_val, k_sp_idx, v_sp_val, v_sp_idx)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "chunk", "scale_factor", "interpret"),
)
def gear_decode_paged(
    q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero, n_comp,
    block_tables,
    k_a=None, k_b=None, v_a=None, v_b=None,
    k_sp_val=None, k_sp_idx=None, v_sp_val=None, v_sp_idx=None,
    *, bits: int, chunk: int, scale_factor: float, interpret: bool = False,
):
    """Paged twin of :func:`gear_decode`: same kernel body, same math, but
    the compressed operands are *head-flattened pool pages* addressed
    through scalar-prefetched block tables instead of contiguous rows.

    Pool operands are ``[P*H, ...one-chunk-block]`` (a pool leaf
    ``[P, H, ...]`` reshaped by the caller): page ``p``, head ``h`` lives at
    row ``p*H + h``.  ``block_tables [B, C]`` arrives via
    ``PrefetchScalarGridSpec`` so every BlockSpec index map can compute its
    DMA source ``row = bt[bh // H, c] * H + bh % H`` before the grid step
    runs — the gather happens in the DMA engine, not as kernel gather ops.
    Pages behind table entries past a row's live chunks never reach the
    math (the body skips them), so the accumulated (acc, m, l) triple is
    bit-identical to :func:`gear_decode` on the gathered-dense cache
    whatever those entries point at.  ``n_comp`` handling is unchanged
    (ragged per-row extents).
    """
    BH, G, Dh = q.shape
    B, C = block_tables.shape
    H = BH // B
    Lp = k_packed.shape[-1]
    use_lr = k_a is not None
    use_sp = k_sp_val is not None
    r = k_a.shape[-1] if use_lr else 1
    ks2 = k_sp_val.shape[-1] if use_sp else 1
    kv2 = v_sp_val.shape[-1] if use_sp else 1
    gv = v_scale.shape[-1]
    nb = chunk
    f32 = jnp.float32

    # page-row index map shared by every pool operand: the chunk coordinate
    # is consumed by the block-table lookup, the block covers the whole page
    def prow(*tail):
        return lambda x, c, bt, n: (
            (bt[x // H, c] * H + x % H).astype(jnp.int32), *tail)

    # dummy single-page operands when the policy has no low-rank / sparse
    # fields; their index maps pin to row 0 so no table lookup happens
    zrow = lambda *tail: (lambda x, c, bt, n: (0, *tail))
    if not use_lr:
        k_a = jnp.zeros((1, nb, 1), f32); k_b = jnp.zeros((1, 1, Dh, 1), f32)
        v_a = jnp.zeros((1, nb, 1), f32); v_b = jnp.zeros((1, 1, Dh, 1), f32)
    if not use_sp:
        k_sp_val = jnp.zeros((1, 1, Dh, 1), f32)
        k_sp_idx = jnp.full((1, 1, Dh, 1), -1, jnp.int32)
        v_sp_val = jnp.zeros((1, nb, 1), f32)
        v_sp_idx = jnp.full((1, nb, 1), -1, jnp.int32)
    lr_row = prow if use_lr else zrow
    sp_row = prow if use_sp else zrow

    n_comp_arr = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (BH,))
    bt = jnp.asarray(block_tables, jnp.int32)

    def kernel(bt_ref, *refs):
        del bt_ref  # consumed by the index maps
        _kernel(*refs, bits=bits, chunk=chunk, scale_factor=scale_factor,
                use_lr=use_lr, use_sp=use_sp)

    bh = lambda x, c, bt, n: (x, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, C),
        in_specs=[
            pl.BlockSpec((1, G, Dh), bh),                          # q
            pl.BlockSpec((1, chunk, Lp), prow(0, 0)),              # k_packed
            pl.BlockSpec((1, 1, Dh), prow(0, 0)),                  # k_scale
            pl.BlockSpec((1, 1, Dh), prow(0, 0)),                  # k_zero
            pl.BlockSpec((1, chunk, Lp), prow(0, 0)),              # v_packed
            pl.BlockSpec((1, chunk, gv), prow(0, 0)),              # v_scale
            pl.BlockSpec((1, chunk, gv), prow(0, 0)),              # v_zero
            pl.BlockSpec((1, chunk, r), lr_row(0, 0)),             # k_a
            pl.BlockSpec((1, 1, Dh, r), lr_row(0, 0, 0)),          # k_b
            pl.BlockSpec((1, chunk, r), lr_row(0, 0)),             # v_a
            pl.BlockSpec((1, 1, Dh, r), lr_row(0, 0, 0)),          # v_b
            pl.BlockSpec((1, 1, Dh, ks2), sp_row(0, 0, 0)),        # k_sp_val
            pl.BlockSpec((1, 1, Dh, ks2), sp_row(0, 0, 0)),        # k_sp_idx
            pl.BlockSpec((1, chunk, kv2), sp_row(0, 0)),           # v_sp_val
            pl.BlockSpec((1, chunk, kv2), sp_row(0, 0)),           # v_sp_idx
        ],
        out_specs=(
            pl.BlockSpec((1, G, Dh), bh),
            pl.BlockSpec((1, G, 128), bh),
            pl.BlockSpec((1, G, 128), bh),
        ),
    )
    out_shape = (
        jax.ShapeDtypeStruct((BH, G, Dh), f32),
        jax.ShapeDtypeStruct((BH, G, 128), f32),
        jax.ShapeDtypeStruct((BH, G, 128), f32),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gear_decode_paged",
    )(bt, n_comp_arr, q, k_packed, k_scale, k_zero, v_packed, v_scale,
      v_zero, k_a, k_b, v_a, v_b, k_sp_val, k_sp_idx, v_sp_val, v_sp_idx)
