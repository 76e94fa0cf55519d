"""PowerSGD low-rank gradient compression for the cross-pod axis.

Vogels et al. (2019) — the same power-iteration solver GEAR cites for its
SVDSolver (Algorithm 2) — applied to distributed training: per-pod partial
gradients are factored as ``G ≈ A Bᵀ`` (rank r, warm-started, with error
feedback), and only the factors cross the inter-pod links (``r·(n+m)``
elements instead of ``n·m``).  In-pod reduction stays exact: the train loop
wraps the step in ``shard_map`` manual only over ``pod``, leaving
``data``/``model`` to the SPMD partitioner (hierarchical reduction).

Matrices with fewer than ``min_size`` elements, and 1-D params, are
all-reduced exactly (compression overhead would dominate).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import lowrank

__all__ = ["CompressorConfig", "init_error_feedback", "compressed_psum"]


@dataclasses.dataclass(frozen=True)
class CompressorConfig:
    rank: int = 8
    power_iters: int = 2
    min_size: int = 65536
    axis: str = "pod"

    def compressible(self, leaf: jnp.ndarray) -> bool:
        return leaf.ndim >= 2 and leaf.size >= self.min_size


def init_error_feedback(params: Any, cfg: CompressorConfig) -> Any:
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32) if cfg.compressible(p) else None,
        params)


def _as_matrix(g: jnp.ndarray) -> jnp.ndarray:
    """Collapse leading dims: [.., n, m] -> [n', m] with m the last dim."""
    return g.reshape(-1, g.shape[-1])


def compressed_psum(grads: Any, ef: Any, cfg: CompressorConfig, key: jax.Array):
    """All-reduce grads over ``cfg.axis`` with PowerSGD compression.

    MUST be called inside shard_map with ``cfg.axis`` a manual axis.
    Returns (mean grads, new error-feedback state, bytes metrics).
    """
    n_dev = jax.lax.axis_size(cfg.axis)
    exact_bytes = jnp.zeros((), jnp.float32)
    comp_bytes = jnp.zeros((), jnp.float32)

    flat, treedef = jax.tree_util.tree_flatten(grads)
    ef_flat = jax.tree_util.tree_flatten(ef, is_leaf=lambda x: x is None)[0]
    out, new_ef = [], []
    for i, (g, e) in enumerate(zip(flat, ef_flat)):
        if not cfg.compressible(g):
            out.append(jax.lax.pmean(g, cfg.axis))
            new_ef.append(None)
            exact_bytes += g.size * 4
            continue
        gm = _as_matrix(g.astype(jnp.float32)) + _as_matrix(e)
        a, b = lowrank.power_iteration(gm, cfg.rank, cfg.power_iters,
                                       jax.random.fold_in(key, i),
                                       orthonormalizer="mgs")
        a = jax.lax.pmean(a, cfg.axis)
        b = jax.lax.pmean(b, cfg.axis)
        approx = lowrank.apply_lowrank(a, b)
        new_ef.append((gm - approx).reshape(g.shape))     # local error feedback
        out.append(approx.reshape(g.shape).astype(g.dtype))
        comp_bytes += (a.size + b.size) * 4
    metrics = {"exact_bytes": exact_bytes, "compressed_bytes": comp_bytes,
               "n_dev": jnp.asarray(n_dev, jnp.float32)}
    return jax.tree_util.tree_unflatten(treedef, out), \
        jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(ef, is_leaf=lambda x: x is None), new_ef), metrics
