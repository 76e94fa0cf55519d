"""Low-rank residual approximation via power iteration (paper Algorithm 2).

The SVDSolver of GEAR is the power-iteration scheme of PowerSGD (Vogels et
al., 2019): a handful of alternating ``A = X B`` / ``B = Xᵀ A`` steps with a
QR orthonormalization on the final sweep.  It returns factors ``A [n, r]``,
``B [d, r]`` with ``A Bᵀ`` close to the best rank-``r`` approximation, at a
fraction of the cost of a full SVD — the property that makes per-decode-chunk
low-rank extraction affordable.

All functions batch over leading dimensions, which is how the paper's
head-wise (and batch-wise) decomposition is realized: callers pass
``[B, H, n, d_head]`` and every head gets its own factors.

The same routine powers the distributed-training gradient compressor
(:mod:`repro.optim.grad_compress`), mirroring the PowerSGD lineage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["power_iteration", "lowrank_approx", "svd_topr", "apply_lowrank"]


def _batched_qr_q(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal basis of the columns of x (batched thin QR)."""
    q, _ = jnp.linalg.qr(x.astype(jnp.float32))
    return q


def _mgs_q(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal basis via modified Gram-Schmidt (pure einsums).

    Slightly weaker numerically than LAPACK QR, but free of custom calls:
    XLA's SPMD partitioner cannot handle LAPACK custom calls inside a
    partially-manual ``shard_map`` region (it aborts with
    ``IsManualSubgroup`` check failures), so the gradient compressor uses
    this path.  Ranks are small (<= 16); the unrolled loop is cheap.
    """
    xf = x.astype(jnp.float32)
    cols = []
    for j in range(xf.shape[-1]):
        v = xf[..., j]
        for q in cols:
            v = v - jnp.sum(q * v, axis=-1, keepdims=True) * q
        norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
        cols.append(v / jnp.maximum(norm, 1e-12))
    return jnp.stack(cols, axis=-1)


def power_iteration(
    x: jnp.ndarray,
    rank: int,
    iters: int = 4,
    key: jax.Array | None = None,
    orthonormalizer: str = "qr",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Approximate top-``rank`` factors of ``x`` [..., n, d].

    Returns (A [..., n, rank], B [..., d, rank]) with ``A @ Bᵀ ≈ x_r``.
    Follows Algorithm 2: QR on B entering the final sweep, QR on A after the
    final ``A = X B``, then ``B = Xᵀ A`` carries the singular values.
    ``orthonormalizer="mgs"`` swaps LAPACK QR for Gram-Schmidt — required
    inside manual ``shard_map`` regions (see :func:`_mgs_q`).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    ortho = _mgs_q if orthonormalizer == "mgs" else _batched_qr_q
    n, d = x.shape[-2], x.shape[-1]
    lead = x.shape[:-2]
    xf = x.astype(jnp.float32)
    # The random init is drawn once at [d, rank] and broadcast over the
    # leading (batch/head/chunk) dims: each matrix's factors then depend only
    # on its own data and the key, never on its position in the batch.  The
    # serving cache relies on this batch-invariance so a request spliced into
    # a live batch compresses bit-identically to a solo run (DESIGN.md).
    b = jnp.broadcast_to(jax.random.normal(key, (d, rank), dtype=jnp.float32),
                         lead + (d, rank))
    a = jnp.zeros(lead + (n, rank), dtype=jnp.float32)
    for l in range(iters):
        last = l == iters - 1
        if last:
            b = ortho(b)
        a = jnp.einsum("...nd,...dr->...nr", xf, b)
        if last:
            a = ortho(a)
        b = jnp.einsum("...nd,...nr->...dr", xf, a)
    return a, b


def lowrank_approx(x: jnp.ndarray, rank: int, iters: int = 4, key=None) -> jnp.ndarray:
    a, b = power_iteration(x, rank, iters, key)
    return apply_lowrank(a, b)


def apply_lowrank(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Materialize ``A @ Bᵀ`` (only used off the fast path / in tests)."""
    return jnp.einsum("...nr,...dr->...nd", a.astype(jnp.float32), b.astype(jnp.float32))


def svd_topr(x: jnp.ndarray, rank: int) -> jnp.ndarray:
    """Exact best rank-r approximation (oracle for tests/benchmarks)."""
    u, s, vt = jnp.linalg.svd(x.astype(jnp.float32), full_matrices=False)
    return jnp.einsum(
        "...nr,...r,...rd->...nd", u[..., :rank], s[..., :rank], vt[..., :rank, :]
    )
