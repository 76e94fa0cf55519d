"""Pallas TPU kernel: fused GEAR chunk compression.

One compression event per grid step, entirely VMEM-resident — the
device-side analogue of the paper's fused CUDA compression path that
KVComp/PackKV show is where the peak-memory/throughput win comes from.
Consumed by streaming chunked prefill
(:func:`repro.core.cache.streaming_prefill_pipeline` via the ``fused``
knob); decode's buffer-close event still runs the plain XLA
``compress_matrix`` path (wiring it through ``append_token`` is future
work).  Per chunk tile ``[n_b, Dh]`` the kernel:

  1. extracts the top/bottom ``k`` magnitude outliers per vector with
     masked max sweeps (pure vector ops, :func:`jax.lax.top_k` ordering)
     and densifies them with sequential compare-iota selects (set
     semantics, matching the oracle's scatter),
  2. quantizes the remainder with the chunk-local uniform asymmetric
     quantizer (per-channel token groups for K, per-token channel groups for
     V — both orientations of :mod:`repro.core.quant`),
  3. packs the codes into int32 lanes (:mod:`repro.core.packing` layout;
     the lane gather is a 0/1 matmul per bit-plane, then shift/or),
  4. emits the quantization residual ``(x − S) − deq(D̂)`` in f32 for the
     XLA-side power-iteration low-rank step (stats are rounded through the
     cache's storage dtype first, by :func:`repro.core.quant.round_to_dtype`
     on both sides, so the residual matches what
     :func:`repro.core.gear.compress_matrix` would hand the SVD solver).

Int codes, min/max stats, and the outlier scratch never touch HBM; the HBM
traffic of one compression event is exactly its compressed output plus one
chunk of input/residual.

Layout contract (shared with :func:`repro.kernels.ref.gear_compress_ref`):

  x [N, n_b, Dh]  ->  packed   int32 [N, n_b, Dh // (32/bits)]
                      scale/zero f32 [N, n_b/g, Dh]   (per_channel, g tokens)
                                     [N, n_b, Dh/g]   (per_token*, g channels)
                      sp_val/idx     [N, Dh, 2k]      (per_channel: token idx)
                                     [N, n_b, 2k]     (per_token*: channel idx)
                      resid      f32 [N, n_b, Dh]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import round_to_dtype

__all__ = ["gear_compress"]


def _topk(x, k: int, axis: int):
    """Top-``k`` of ``x`` [nb, d] along ``axis`` by ``k`` masked max sweeps.

    The in-kernel form of the oracle's :func:`jax.lax.top_k` (same order:
    values descending, ties to the lower index), returning lists of ``k``
    keepdims vectors so no lane<->sublane stacking is needed.  The index
    argmin runs in f32, which holds every index exactly."""
    n = x.shape[axis]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    iota_f = iota.astype(jnp.float32)
    work = x
    vals, idxs = [], []
    for _ in range(k):
        v = jnp.max(work, axis=axis, keepdims=True)
        i = jnp.min(jnp.where(work == v, iota_f, float(n)), axis=axis,
                    keepdims=True).astype(jnp.int32)
        vals.append(v)
        idxs.append(i)
        work = jnp.where(iota == i, -3.4e38, work)
    return vals, idxs


def _group_minmax(r, group: int, per_channel: bool):
    """Per-group (min, max) of ``r`` [nb, d]: groups of ``group`` tokens per
    channel (K) or ``group`` channels per token (V).  Whole-vector groups
    reduce with keepdims; finer groups reshape the reduced axis."""
    nb, d = r.shape
    if per_channel:
        if group == nb:
            return (jnp.min(r, axis=0, keepdims=True),
                    jnp.max(r, axis=0, keepdims=True))       # [1, d]
        rg = r.reshape(nb // group, group, d)
        return jnp.min(rg, axis=1), jnp.max(rg, axis=1)      # [nb/g, d]
    if group == d:
        return (jnp.min(r, axis=1, keepdims=True),
                jnp.max(r, axis=1, keepdims=True))           # [nb, 1]
    rg = r.reshape(nb, d // group, group)
    return jnp.min(rg, axis=2), jnp.max(rg, axis=2)          # [nb, d/g]


def _expand(stat, group: int, per_channel: bool, shape):
    """Broadcast per-group stats back over the [nb, d] tile."""
    nb, d = shape
    if per_channel:
        if stat.shape[0] == 1:
            return stat
        return jnp.broadcast_to(stat[:, None, :], (nb // group, group, d)
                                ).reshape(nb, d)
    if stat.shape[1] == 1:
        return stat
    return jnp.broadcast_to(stat[:, :, None], (nb, d // group, group)
                            ).reshape(nb, d)


def _pack(codes, bits: int):
    """codes f32 [nb, d] (integers in [0, 2**bits)) -> int32 [nb, d/per].

    Lane ``i`` holds codes ``i*per + j`` at bits ``[j*bits, (j+1)*bits)``
    (:mod:`repro.core.packing`).  Gathering every ``per``-th lane is a 0/1
    matmul per bit-plane — exact, since each output sums one code that a
    bf16 pass already represents — which keeps the lane shuffle on the MXU
    instead of in a lane-splitting reshape Mosaic cannot lower."""
    nb, d = codes.shape
    per = 32 // bits
    lanes = d // per
    src = jax.lax.broadcasted_iota(jnp.int32, (d, lanes), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (d, lanes), 1)
    packed = jnp.zeros((nb, lanes), jnp.int32)
    for j in range(per):
        pick = (src == dst * per + j).astype(jnp.float32)
        plane = jnp.dot(codes, pick, preferred_element_type=jnp.float32)
        packed = packed | (plane.astype(jnp.int32) << (j * bits))
    return packed


def _kernel(x_ref, *refs, bits: int, group: int, per_channel: bool,
            n_out: int, stat_dtype: str):
    if n_out:
        packed_ref, scale_ref, zero_ref, spv_ref, spi_ref, resid_ref = refs
    else:
        packed_ref, scale_ref, zero_ref, resid_ref = refs
    x = x_ref[0].astype(jnp.float32)                     # [nb, d]
    nb, d = x.shape

    # ---- outliers: top/bottom k per vector, densified via select chain ----
    # sp_* blocks are [2k, d] for K (one outlier rank per sublane row) and
    # [nb, 2k] for V (one per lane column): each store keeps the vector's
    # natural orientation
    r = x
    if n_out:
        axis = 0 if per_channel else 1
        top_v, top_i = _topk(x, n_out, axis)
        bot_v, bot_i = _topk(-x, n_out, axis)
        iota = jax.lax.broadcasted_iota(jnp.int32, (nb, d), axis)
        dense = jnp.zeros((nb, d), jnp.float32)
        # sequential selects = the oracle's scatter-set (top first, then
        # bottom; a position in both sets carries the same value either way)
        for j in range(n_out):
            dense = jnp.where(iota == top_i[j], top_v[j], dense)
        for j in range(n_out):
            dense = jnp.where(iota == bot_i[j], -bot_v[j], dense)
        r = x - dense
        for j, (v, i) in enumerate(zip(top_v + [-b for b in bot_v],
                                       top_i + bot_i)):
            if per_channel:
                spv_ref[0, j:j + 1, :] = v
                spi_ref[0, j:j + 1, :] = i
            else:
                spv_ref[0, :, j:j + 1] = v
                spi_ref[0, :, j:j + 1] = i

    # ---- quantize the remainder (chunk-local groups) ----------------------
    mn, mx = _group_minmax(r, group, per_channel)
    scale = jnp.maximum((mx - mn) / (2**bits - 1), 1e-8)
    codes = jnp.clip(jnp.round((r - _expand(mn, group, per_channel, (nb, d)))
                               / _expand(scale, group, per_channel, (nb, d))),
                     0, 2**bits - 1)

    # ---- pack into int32 lanes -------------------------------------------
    packed_ref[0] = _pack(codes, bits)
    scale_ref[0] = scale
    zero_ref[0] = mn

    # ---- residual for the low-rank step ----------------------------------
    # deq uses the stats as the cache will store them (bf16 by default), so
    # the residual — hence the power-iteration factors — matches the oracle.
    s_r = round_to_dtype(scale, stat_dtype)
    z_r = round_to_dtype(mn, stat_dtype)
    deq = (codes * _expand(s_r, group, per_channel, (nb, d))
           + _expand(z_r, group, per_channel, (nb, d)))
    resid_ref[0] = r - deq


@functools.partial(
    jax.jit,
    static_argnames=("bits", "scheme", "group", "n_out", "stat_dtype",
                     "interpret"),
)
def gear_compress(x: jnp.ndarray, *, bits: int, scheme: str,
                  group: int | None = None, n_out: int = 0,
                  stat_dtype: str = "bfloat16", interpret: bool = False):
    """Fused quantize+pack+stats+outlier compression of a chunk batch.

    x: [N, nb, d].  ``scheme`` is a :mod:`repro.core.quant` scheme name
    (``per_channel`` = K orientation, ``per_token``/``per_token_group`` = V
    orientation); ``group=None`` selects the coarse per-vector grouping.
    ``n_out`` is the per-extreme outlier count (0 disables the sparse path).
    Returns (packed, scale, zero, sp_val, sp_idx, resid) — sp_* are None
    when ``n_out == 0``.  See :func:`repro.kernels.ref.gear_compress_ref`
    for the oracle defining the exact contract.
    """
    N, nb, d = x.shape
    per = 32 // bits
    per_channel = scheme == "per_channel"
    if group is None:
        group = nb if per_channel else d
    rows, cols = (nb // group, d) if per_channel else (nb, d // group)
    f32 = jnp.float32
    out_shape = [
        jax.ShapeDtypeStruct((N, nb, d // per), jnp.int32),
        jax.ShapeDtypeStruct((N, rows, cols), f32),
        jax.ShapeDtypeStruct((N, rows, cols), f32),
    ]
    out_specs = [
        pl.BlockSpec((1, nb, d // per), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, rows, cols), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, rows, cols), lambda i: (i, 0, 0)),
    ]
    if n_out:
        # K outliers leave the kernel [N, 2k, d] and are transposed to the
        # cache's [N, d, 2k] below
        sp_shape = (2 * n_out, d) if per_channel else (nb, 2 * n_out)
        out_shape += [
            jax.ShapeDtypeStruct((N,) + sp_shape, f32),
            jax.ShapeDtypeStruct((N,) + sp_shape, jnp.int32),
        ]
        out_specs += [
            pl.BlockSpec((1,) + sp_shape, lambda i: (i, 0, 0)),
            pl.BlockSpec((1,) + sp_shape, lambda i: (i, 0, 0)),
        ]
    out_shape.append(jax.ShapeDtypeStruct((N, nb, d), f32))
    out_specs.append(pl.BlockSpec((1, nb, d), lambda i: (i, 0, 0)))

    kernel = functools.partial(
        _kernel, bits=bits, group=group, per_channel=per_channel,
        n_out=n_out, stat_dtype=stat_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, nb, d), lambda i: (i, 0, 0))],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="gear_compress",
    )(x)
    if n_out:
        packed, scale, zero, spv, spi, resid = out
        if per_channel:
            spv, spi = jnp.swapaxes(spv, 1, 2), jnp.swapaxes(spi, 1, 2)
        return packed, scale, zero, spv, spi, resid
    packed, scale, zero, resid = out
    return packed, scale, zero, None, None, resid
