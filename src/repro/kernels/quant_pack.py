"""Pallas TPU kernel: fused per-channel quantize + bit-pack.

One compression event (a streaming-buffer chunk) per grid step: the chunk
tile lives in VMEM, min/max reductions run on the VPU, the quantize +
shift/or pack is fully vectorized, and packed int32 lanes + scale/zero are
written back without ever materializing int codes in HBM — the fusion the
paper implements in CUDA for the quantization path.

Layout matches :func:`repro.kernels.ref.quant_pack_ref`:
  x [N, n, d]  ->  packed int32 [N, n, d//per], scale/zero f32 [N, d]
  (per = 32 // bits; groups = whole columns of the chunk)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quant_pack"]


def _kernel(x_ref, packed_ref, scale_ref, zero_ref, *, bits: int):
    x = x_ref[0].astype(jnp.float32)            # [n, d]
    n, d = x.shape
    per = 32 // bits
    mn = jnp.min(x, axis=0)                      # [d]
    mx = jnp.max(x, axis=0)
    scale = jnp.maximum((mx - mn) / (2**bits - 1), 1e-8)
    codes = jnp.clip(jnp.round((x - mn[None, :]) / scale[None, :]),
                     0, 2**bits - 1).astype(jnp.uint32)
    lanes = codes.reshape(n, d // per, per)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * bits)[None, None, :]
    packed = jnp.sum(lanes << shifts, axis=-1, dtype=jnp.uint32)
    packed_ref[0] = packed.astype(jnp.int32)
    scale_ref[0] = scale
    zero_ref[0] = mn


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quant_pack(x: jnp.ndarray, bits: int, interpret: bool = False):
    """x: [N, n, d] -> (packed [N, n, d//per] int32, scale [N, d], zero [N, d])."""
    N, n, d = x.shape
    per = 32 // bits
    grid = (N,)
    out_shapes = (
        jax.ShapeDtypeStruct((N, n, d // per), jnp.int32),
        jax.ShapeDtypeStruct((N, d), jnp.float32),
        jax.ShapeDtypeStruct((N, d), jnp.float32),
    )
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((1, n, d), lambda i: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, n, d // per), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
        name="quant_pack",
    )(x)
