"""Uniform asymmetric quantization backbones for KV caches.

Implements the three quantization schemes the paper builds on / compares
against, over tensors laid out ``[..., n, d]`` (n = tokens, d = channels):

* ``per_token_group`` — FlexGen-style: each token row split into contiguous
  groups of ``g`` channels; scale/zero per group.                      (2)
* ``per_channel``     — K-cache orientation (KIVI/KCVT): groups of ``g``
  tokens within one channel column.  ``g = n`` gives the coarse KCVT
  per-vector grouping; ``g = 64`` gives KIVI fine-grained grouping.
* ``per_token``       — V-cache orientation: groups of ``g`` channels within
  one token row.  ``g = d`` gives coarse KCVT; ``g = 64`` gives KIVI.

All schemes share the uniform quantizer of Eq. (2) of the paper:
``x̂ = round((x - min) / Δ)``, ``Δ = (max - min) / (2^b - 1)``, codes packed
into int32 lanes (:mod:`repro.core.packing`).  Dequantization restores
``x ≈ codes · Δ + min``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import packing

__all__ = [
    "QuantizedTensor",
    "quantize",
    "dequantize",
    "round_to_dtype",
    "quant_error",
    "SCHEMES",
]

SCHEMES = ("per_token_group", "per_channel", "per_token")

_EPS = 1e-8


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["packed", "scale", "zero"],
    meta_fields=["bits", "scheme", "group", "n", "d"],
)
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Packed quantized tensor plus the metadata to invert it.

    packed : int32 [..., n, d // (32/bits)]
    scale  : f32/bf16 broadcastable group scales
    zero   : same shape as scale (the group minimum)
    """

    packed: jnp.ndarray
    scale: jnp.ndarray
    zero: jnp.ndarray
    bits: int
    scheme: str
    group: int
    n: int
    d: int

    @property
    def nbytes_packed(self) -> int:
        return self.packed.size * 4

    def size_bytes(self) -> int:
        """Total compressed bytes (codes + scales + zeros)."""
        return self.nbytes_packed + self.scale.size * 2 + self.zero.size * 2


def round_to_dtype(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """``x.astype(dtype).astype(float32)``, in a form no compiler drops.

    XLA may fold a narrowing convert followed by a widening one into a
    no-op (excess precision), while a Pallas kernel keeps both, so the two
    would disagree on what a stat rounded for storage is.  bfloat16 is
    rounded to nearest even on the int32 view of ``x`` (finite values);
    float32 is returned as is.  Runs in XLA and inside Pallas kernels.
    """
    x = x.astype(jnp.float32)
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x
    if dtype != jnp.bfloat16:
        raise ValueError(f"stat dtype must be bfloat16 or float32, got {dtype}")
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.int32(-0x10000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _group_minmax(x: jnp.ndarray, scheme: str, group: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (min, max) broadcast back to x's shape for the given scheme."""
    n, d = x.shape[-2], x.shape[-1]
    lead = x.shape[:-2]
    if scheme in ("per_token_group", "per_token"):
        if d % group != 0:
            raise ValueError(f"d={d} not divisible by group={group}")
        xg = x.reshape(lead + (n, d // group, group))
        mn = jnp.min(xg, axis=-1, keepdims=True)
        mx = jnp.max(xg, axis=-1, keepdims=True)
        return (
            jnp.broadcast_to(mn, xg.shape).reshape(x.shape),
            jnp.broadcast_to(mx, xg.shape).reshape(x.shape),
        )
    if scheme == "per_channel":
        if n % group != 0:
            raise ValueError(f"n={n} not divisible by group={group}")
        xg = x.reshape(lead + (n // group, group, d))
        mn = jnp.min(xg, axis=-2, keepdims=True)
        mx = jnp.max(xg, axis=-2, keepdims=True)
        return (
            jnp.broadcast_to(mn, xg.shape).reshape(x.shape),
            jnp.broadcast_to(mx, xg.shape).reshape(x.shape),
        )
    raise ValueError(f"unknown scheme {scheme!r}; expected one of {SCHEMES}")


def _compact_groups(full: jnp.ndarray, scheme: str, group: int) -> jnp.ndarray:
    """Collapse a broadcast per-entry stat down to one value per group."""
    n, d = full.shape[-2], full.shape[-1]
    lead = full.shape[:-2]
    if scheme in ("per_token_group", "per_token"):
        return full.reshape(lead + (n, d // group, group))[..., 0]
    return full.reshape(lead + (n // group, group, d))[..., 0, :]


def _expand_groups(compact: jnp.ndarray, scheme: str, group: int, n: int, d: int) -> jnp.ndarray:
    lead = compact.shape[: -2 if scheme == "per_channel" else -2]
    if scheme in ("per_token_group", "per_token"):
        x = jnp.repeat(compact[..., None], group, axis=-1)
        return x.reshape(lead + (n, d))
    x = jnp.repeat(compact[..., None, :], group, axis=-2)
    return x.reshape(lead + (n, d))


def quantize(
    x: jnp.ndarray,
    bits: int,
    scheme: str,
    group: int | None = None,
    stat_dtype: jnp.dtype = jnp.float32,
) -> QuantizedTensor:
    """Quantize ``x`` [..., n, d] with the given scheme.

    ``group=None`` selects the coarse per-vector grouping (KCVT): the whole
    channel column for ``per_channel``, the whole token row for ``per_token``.
    """
    n, d = x.shape[-2], x.shape[-1]
    if group is None:
        group = n if scheme == "per_channel" else d
    xf = x.astype(jnp.float32)
    mn_full, mx_full = _group_minmax(xf, scheme, group)
    scale_full = (mx_full - mn_full) / (2**bits - 1)
    scale_full = jnp.maximum(scale_full, _EPS)
    codes = jnp.clip(
        jnp.round((xf - mn_full) / scale_full), 0, 2**bits - 1
    ).astype(jnp.int32)
    packed = packing.pack(codes, bits)
    # rounded explicitly first, so a caller that dequantizes in the same
    # program sees the stored stats even where XLA skips the convert pair
    scale = round_to_dtype(_compact_groups(scale_full, scheme, group),
                           stat_dtype).astype(stat_dtype)
    zero = round_to_dtype(_compact_groups(mn_full, scheme, group),
                          stat_dtype).astype(stat_dtype)
    return QuantizedTensor(
        packed=packed, scale=scale, zero=zero,
        bits=bits, scheme=scheme, group=group, n=n, d=d,
    )


def dequantize(qt: QuantizedTensor, dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    codes = packing.unpack(qt.packed, qt.bits, qt.d).astype(jnp.float32)
    scale = _expand_groups(qt.scale.astype(jnp.float32), qt.scheme, qt.group, qt.n, qt.d)
    zero = _expand_groups(qt.zero.astype(jnp.float32), qt.scheme, qt.group, qt.n, qt.d)
    return (codes * scale + zero).astype(dtype)


def quant_error(x: jnp.ndarray, bits: int, scheme: str, group: int | None = None) -> jnp.ndarray:
    """Frobenius-norm relative error of plain quantization (for benchmarks)."""
    qt = quantize(x, bits, scheme, group)
    xh = dequantize(qt)
    return jnp.linalg.norm(x - xh) / jnp.maximum(jnp.linalg.norm(x), _EPS)
