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

Grid: (BH, C) over the dense layout's rows.  Block shapes are MXU/VPU
aligned: Dh ∈ {64, 128, 256} maps to lane-dim 128 tiles; the chunk dim
(n_b = 64/128) is the sublane dim.

**Paged layout.**  :func:`gear_decode_paged` reads the pool's lane-dense
pages (``repro.core.cache.page_layout``): two leaves, ``codes`` (int32
token-major code words) and ``side`` (every other field, long axis on
lanes), ``[rows, 128]`` a page.  Its grid is (B, C): a step DMAs one
page of each leaf for one slot's chunk and works all of the slot's KV
heads.  Each head's fields are unfolded from the page rows into the tiles
and factors the dense layout's de-quantization builds (codes by whole-vreg
shifts, per-token V rows turned to columns and low-rank factors to the
dense orientation by small transposes in VMEM); from there both layouts
run the same online-softmax update (:func:`_attend_chunk`).

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

from repro.core.cache import LANES, unfold_rows, unpack_words

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


# ---------------------------------------------------------------------------
# The chunk's math, shared by both layouts: each layout's de-quantization
# rebuilds the same f32 tiles and factors, then one online-softmax update.


def _k_tile(codes, scale, zero, sp):
    """K tile [nb, Dh] from codes [nb, Dh] f32, per-channel ``scale`` /
    ``zero`` [1, Dh] and the outliers ``sp = (val, idx)`` [Ks, Dh] (token
    index of channel d's j-th outlier at (j, d)), or None."""
    k = codes * scale + zero
    if sp is not None:
        val, idx = sp
        row = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        for j in range(val.shape[0]):
            k += jnp.where(row == idx[j:j + 1], val[j:j + 1], 0.0)
    return k


def _v_tile(codes, scale, zero, sp):
    """V tile [nb, Dh] from codes [nb, Dh] f32, per-token ``scale`` /
    ``zero`` [nb, Dh] and the outliers ``sp = (val, idx)`` [nb, Kv]
    (channel index of token t's j-th outlier at (t, j)), or None."""
    v = codes * scale + zero
    if sp is not None:
        val, idx = sp
        col = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        for j in range(val.shape[-1]):
            v += jnp.where(col == idx[:, j][:, None], val[:, j][:, None], 0.0)
    return v


def _attend_chunk(q, k, v, lr, acc, m, l, tok0, n, *, scale_factor: float):
    """One live chunk's online-softmax update of a (acc [G, Dh], m, l
    [G, 128]) triple: scores of ``q`` [G, Dh] against the chunk's K tile
    plus the factored low-rank path, masked past extent ``n`` (the chunk's
    first token is ``tok0``), accumulated against its V tile.

    ``lr`` is None or the factors ``(k_a, k_b, v_a, v_b)``: ``[nb, r]``,
    ``[Dh, r]``, ``[nb, r]``, ``[Dh, r]``.
    """
    f32 = jnp.float32
    dot = lambda a, b, ca, cb: jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=f32)
    G, nb = q.shape[0], k.shape[0]
    s = dot(q, k, 1, 1)                                        # [G, nb]
    if lr is not None:
        ka, kb, va, vb = lr
        qb = dot(q, kb, 1, 0)                                  # [G, r]
        s += dot(qb, ka, 1, 1)
    s = s * scale_factor
    tok = tok0 + jax.lax.broadcasted_iota(jnp.int32, (G, nb), 1)
    s = jnp.where(tok < n, s, NEG_INF)

    m_prev = m[:, 0]                                           # [G]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                            # [G, nb]
    l = l * corr[:, None] + jnp.sum(p, axis=-1)[:, None]
    pv = dot(p, v, 1, 0)
    if lr is not None:
        pa = dot(p, va, 1, 0)                                  # [G, r]
        pv += dot(pa, vb, 1, 1)
    acc = acc * corr[:, None] + pv
    return acc, jnp.broadcast_to(m_new[:, None], m.shape), l


def _init_triple(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


# ---------------------------------------------------------------------------
# Dense layout: grid (BH, C), one (slot, head) row's chunk a step


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
        _init_triple(acc_ref, m_ref, l_ref)

    # live chunks are the first ceil(n / chunk); a chunk past them would
    # add corr = 1, p = 0, so its step runs no body
    @pl.when(c * chunk < n)
    def _chunk():
        f32 = lambda ref: ref.astype(jnp.float32)
        Dh = q_ref.shape[-1]
        k_sp = v_sp = lr = None
        if use_sp:
            # [Dh, Ks] -> [Ks, Dh]: channel on lanes, like the tile
            k_sp = (f32(ksv_ref[0, 0]).T, ksi_ref[0, 0].T)
            v_sp = (f32(vsv_ref[0]), vsi_ref[0])               # [nb, Kv]
        if use_lr:
            lr = (f32(ka_ref[0]), f32(kb_ref[0, 0]),           # [nb, r], [Dh, r]
                  f32(va_ref[0]), f32(vb_ref[0, 0]))
        k = _k_tile(_unpack(kp_ref[0], bits, Dh), _window_row(ks_ref, c),
                    _window_row(kz_ref, c), k_sp)
        gv = vs_ref.shape[-1]
        v = _v_tile(_unpack(vp_ref[0], bits, Dh),
                    jnp.repeat(f32(vs_ref[0]), Dh // gv, axis=-1),
                    jnp.repeat(f32(vz_ref[0]), Dh // gv, axis=-1), v_sp)
        acc_ref[0], m_ref[0], l_ref[0] = _attend_chunk(
            f32(q_ref[0]), k, v, lr, acc_ref[0], m_ref[0], l_ref[0],
            c * chunk, n, scale_factor=scale_factor)


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


# ---------------------------------------------------------------------------
# Paged layout: grid (B, C), one slot's chunk, every KV head, a step


def _page_field(blk, f, h: int, transposed: bool):
    """Head ``h``'s field ``f`` (a :class:`~repro.core.cache.PageField`) out
    of a page block ``blk`` [rows, 128], transposed against the dense chunk
    field iff ``transposed``."""
    x = unfold_rows(blk[f.offset + h * f.rows:f.offset + (h + 1) * f.rows], f)
    return x.T if transposed != f.transposed else x


def _page_codes(words, f, h: int, bits: int, chunk: int):
    """Head ``h``'s codes [chunk, Dh] f32 from the page's code words."""
    x = _page_field(words, f, h, False)                        # [a, Dh]
    return unpack_words(x, bits, chunk).astype(jnp.float32)


def _dequant_page(words, side, h: int, layout, dh: int, pin: bool):
    """Head ``h``'s K and V tiles [chunk, Dh] and low-rank factors (or
    None) from one page's ``codes`` words and f32 ``side`` rows: the values
    the dense layout's de-quantization builds, bit for bit."""
    nb, bits = layout.chunk, layout.bits
    get = lambda name, t: _page_field(side, layout.field(name), h, t)
    k_sp = v_sp = lr = None
    if layout.field("k_sp_val") is not None:
        k_sp = (get("k_sp_val", True),                          # [Ks, Dh]
                get("k_sp_idx", True).astype(jnp.int32))
        v_sp = (get("v_sp_val", False),                         # [nb, Kv]
                get("v_sp_idx", False).astype(jnp.int32))
    if layout.field("k_a") is not None:
        lr = tuple(get(n, False) for n in ("k_a", "k_b", "v_a", "v_b"))
        if pin:
            # interpreted, XLA would fold these transposes into the dots and
            # sum the rank-r contraction in another order than the dense
            # kernel's; Mosaic folds nothing (and lowers no barrier)
            lr = jax.lax.optimization_barrier(lr)
    k = _k_tile(_page_codes(words, layout.field("k_packed"), h, bits, nb),
                get("k_scale", False), get("k_zero", False), k_sp)
    vs, vz = get("v_scale", False), get("v_zero", False)       # [nb, gv]
    rep = dh // vs.shape[-1]
    v = _v_tile(_page_codes(words, layout.field("v_packed"), h, bits, nb),
                jnp.repeat(vs, rep, axis=-1), jnp.repeat(vz, rep, axis=-1),
                v_sp)
    return k, v, lr


def _paged_kernel(bt_ref, n_comp_ref, layer_ref, q_ref, codes_ref, side_ref,
                  acc_ref, m_ref, l_ref, *, layout, scale_factor: float,
                  interpret: bool):
    del bt_ref, layer_ref  # consumed by the index maps
    b = pl.program_id(0)
    c = pl.program_id(1)
    n = n_comp_ref[b]
    chunk = layout.chunk

    @pl.when(c == 0)
    def _init():
        _init_triple(acc_ref, m_ref, l_ref)

    @pl.when(c * chunk < n)
    def _chunk():
        words = codes_ref[0]                                   # [Rc, 128]
        side = side_ref[0].astype(jnp.float32)                 # [Rs, 128]
        for h in range(layout.heads):
            q = q_ref[0, h].astype(jnp.float32)                # [G, Dh]
            k, v, lr = _dequant_page(words, side, h, layout, q.shape[-1],
                                     pin=interpret)
            acc_ref[0, h], m_ref[0, h], l_ref[0, h] = _attend_chunk(
                q, k, v, lr, acc_ref[0, h], m_ref[0, h], l_ref[0, h],
                c * chunk, n, scale_factor=scale_factor)


@functools.partial(
    jax.jit, static_argnames=("layout", "scale_factor", "interpret"))
def gear_decode_paged(q, codes, side, n_comp, block_tables, layer=0, *,
                      layout, scale_factor: float, interpret: bool = False):
    """Paged twin of :func:`gear_decode`: the same math over lane-dense
    pool pages addressed through scalar-prefetched block tables.

    ``codes`` and ``side`` are the pool's leaves ``[P, rows, 128]``, or the
    pools of several layers stacked ``[L, P, rows, 128]`` of which the
    kernel reads layer ``layer``; ``layout`` places the fields in a page
    (``repro.core.cache.page_layout``: every field of a page, every KV
    head, in whole tiles).  ``q`` is ``[B*H, G, Dh]`` (slot-major rows, as
    :func:`gear_decode` takes them); ``n_comp`` is a scalar or a per-slot
    ``[B]`` compressed extent; ``block_tables [B, C]`` arrives via
    ``PrefetchScalarGridSpec`` so each index map computes its DMA source
    page ``bt[b, c]`` before the grid step runs — the gather happens in the
    DMA engine, two DMAs a step (codes, side), not kernel gather ops.  One
    grid step takes one slot's chunk for all its heads.  Pages behind table
    entries past a slot's live chunks never reach the math (the body skips
    them), so the (acc, m, l) triple is bit-identical to :func:`gear_decode`
    on the gathered-dense cache whatever those entries point at.  Returns
    ``(acc [B*H, G, Dh], m, l [B*H, G, 128])``.
    """
    BH, G, Dh = q.shape
    B, C = block_tables.shape
    H = layout.heads
    f32 = jnp.float32
    if codes.ndim == 3:
        codes, side = codes[None], side[None]
    n_comp_arr = jnp.broadcast_to(jnp.asarray(n_comp, jnp.int32), (B,))
    bt = jnp.asarray(block_tables, jnp.int32)
    lay = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    kernel = functools.partial(_paged_kernel, layout=layout,
                               scale_factor=scale_factor, interpret=interpret)
    slot = lambda b, c, bt, n, lay: (b, 0, 0, 0)
    page = lambda b, c, bt, n, lay: (lay[0], bt[b, c], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, C),
        in_specs=[
            pl.BlockSpec((1, H, G, Dh), slot),                     # q
            pl.BlockSpec((None, 1) + codes.shape[2:], page),       # codes
            pl.BlockSpec((None, 1) + side.shape[2:], page),        # side
        ],
        out_specs=(
            pl.BlockSpec((1, H, G, Dh), slot),
            pl.BlockSpec((1, H, G, LANES), slot),
            pl.BlockSpec((1, H, G, LANES), slot),
        ),
    )
    out_shape = (
        jax.ShapeDtypeStruct((B, H, G, Dh), f32),
        jax.ShapeDtypeStruct((B, H, G, LANES), f32),
        jax.ShapeDtypeStruct((B, H, G, LANES), f32),
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gear_decode_paged",
    )(bt, n_comp_arr, lay, q.reshape(B, H, G, Dh), codes, side)
    return (acc.reshape(BH, G, Dh), m.reshape(BH, G, LANES),
            l.reshape(BH, G, LANES))
