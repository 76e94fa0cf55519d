"""Per-vector outlier extraction (the sparse matrix ``S`` of GEAR, Eq. 4).

``Filter_s`` keeps the top ``s/2`` % and bottom ``s/2`` % magnitude-extreme
entries of each vector in full precision:

* K-cache orientation (``axis="token"``): vectors are **channels**; for each
  channel we filter along the token axis.
* V-cache orientation (``axis="channel"``): vectors are **tokens**; for each
  token we filter along the channel axis.

For a JIT-static representation, the fraction ``s`` maps to a fixed count
``k = ceil(s/2 · vec_len)`` per extreme, stored as (values, int32 indices)
pairs of capacity ``2k`` per vector.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["SparseOutliers", "outlier_count", "filter_outliers",
           "filter_outliers_k", "densify"]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["values", "indices"],
    meta_fields=["axis", "n", "d", "k"],
)
@dataclasses.dataclass(frozen=True)
class SparseOutliers:
    """Fixed-capacity sparse outlier set for a [..., n, d] tensor.

    axis="token":  values/indices are [..., d, 2k], indices in [0, n)
    axis="channel": values/indices are [..., n, 2k], indices in [0, d)
    """

    values: jnp.ndarray
    indices: jnp.ndarray
    axis: str
    n: int
    d: int
    k: int

    def size_bytes(self) -> int:
        # fp16 value + int32 index per kept entry (paper stores index vectors
        # in full precision; we use int32 which is what the table accounting
        # assumes for "2 index vectors + 1 value vector").
        return self.values.size * 2 + self.indices.size * 4


def outlier_count(vec_len: int, s: float) -> int:
    """Entries kept per extreme for sparsity fraction ``s`` (e.g. 0.02)."""
    return max(1, math.ceil(vec_len * s / 2.0))


def _scatter_last(shape, idx: jnp.ndarray, vals: jnp.ndarray, dtype) -> jnp.ndarray:
    """Scatter ``vals`` at ``idx`` along the last axis of a zeros(shape)."""
    lead = shape[:-1]
    length = shape[-1]
    flat_rows = 1
    for s in lead:
        flat_rows *= s
    k = idx.shape[-1]
    fidx = idx.reshape(flat_rows, k)
    fval = vals.reshape(flat_rows, k).astype(dtype)
    rows = jnp.arange(flat_rows, dtype=jnp.int32)[:, None]
    out = jnp.zeros((flat_rows, length), dtype=dtype)
    out = out.at[rows, fidx].set(fval)
    return out.reshape(shape)


def filter_outliers(x: jnp.ndarray, s: float, axis: str) -> tuple[SparseOutliers, jnp.ndarray]:
    """Split ``x`` [..., n, d] into (outliers S, remainder x - S).

    Returns the sparse set and the tensor with outlier positions zeroed,
    matching the paper's ``Quant(X - S)`` usage.  The fraction ``s`` maps to
    the fixed per-extreme count of :func:`outlier_count`;
    :func:`filter_outliers_k` is the count-level entry point shared with the
    fused compression kernel's oracle.
    """
    n, d = x.shape[-2], x.shape[-1]
    vec_len = n if axis == "token" else d
    return filter_outliers_k(x, outlier_count(vec_len, s), axis)


def filter_outliers_k(x: jnp.ndarray, k: int, axis: str) -> tuple[SparseOutliers, jnp.ndarray]:
    """:func:`filter_outliers` with the per-extreme count ``k`` given directly."""
    n, d = x.shape[-2], x.shape[-1]
    if axis == "token":
        xt = jnp.swapaxes(x, -1, -2)  # [..., d, n]
        vec_len = n
    elif axis == "channel":
        xt = x
        vec_len = d
    else:
        raise ValueError(f"axis must be 'token' or 'channel', got {axis!r}")
    if 2 * k > vec_len:
        raise ValueError(f"2k={2 * k} exceeds vector length {vec_len}")
    top_v, top_i = jax.lax.top_k(xt, k)
    bot_v_neg, bot_i = jax.lax.top_k(-xt, k)
    values = jnp.concatenate([top_v, -bot_v_neg], axis=-1)
    indices = jnp.concatenate([top_i, bot_i], axis=-1).astype(jnp.int32)
    dense_t = _scatter_last(xt.shape, indices, values, x.dtype)
    remainder_t = xt - dense_t
    if axis == "token":
        remainder = jnp.swapaxes(remainder_t, -1, -2)
    else:
        remainder = remainder_t
    sp = SparseOutliers(values=values, indices=indices, axis=axis, n=n, d=d, k=k)
    return sp, remainder


def densify(sp: SparseOutliers, dtype=jnp.float32) -> jnp.ndarray:
    """Reconstruct the dense [..., n, d] sparse matrix S."""
    if sp.axis == "token":
        lead = sp.values.shape[:-2]
        dense_t = _scatter_last(lead + (sp.d, sp.n), sp.indices, sp.values, dtype)
        return jnp.swapaxes(dense_t, -1, -2)
    lead = sp.values.shape[:-2]
    return _scatter_last(lead + (sp.n, sp.d), sp.indices, sp.values, dtype)
