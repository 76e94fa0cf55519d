"""Pallas TPU kernel: chunked linear-recurrence scan (RWKV6 / Mamba-2 SSD).

The training hot path of the attention-free archs (rwkv6-3b, hymba-1.5b's
SSM heads).  Grid is (BH, chunks) with the chunk dim innermost and the
per-head state carried in VMEM scratch across grid steps — the sequential
dependency never leaves VMEM, while the intra-chunk work is three
MXU matmuls on [W, Dk]×[W, Dv] tiles (the same GLA-style factorization as
:func:`repro.models.linear_scan.chunked_scan`, which is the oracle).

Computes, per head, with decay w_t ∈ (0, 1]:
    S_t = diag(w_t)·S_{t-1} + k_t v_tᵀ
    y_t = r_tᵀ·S_t                           (mode="inclusive", Mamba)
    y_t = r_tᵀ·(S_{t-1} + diag(u) k_t v_tᵀ)  (mode="bonus", RWKV6)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["linear_scan_chunked"]

CLAMP = 30.0


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, state_out_ref, state_scr,
            *, chunk: int, n_chunks: int, mode: str):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    f32 = jnp.float32
    r = r_ref[0].astype(f32)            # [W, Dk]
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)            # [W, Dv]
    lw = lw_ref[0].astype(f32)          # [W, Dk]
    W, Dk = r.shape

    cum = jnp.cumsum(lw, axis=0)
    q_cum = cum if mode == "inclusive" else cum - lw
    tri = jnp.tril(jnp.ones((W, W), f32), 0 if mode == "inclusive" else -1)

    q_fac = r * jnp.exp(jnp.maximum(q_cum, -CLAMP))
    k_fac = k * jnp.exp(jnp.minimum(-cum, CLAMP))
    att = jax.lax.dot_general(q_fac, k_fac, (((1,), (1,)), ((), ())),
                              preferred_element_type=f32) * tri
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
    if mode == "bonus":
        u = u_ref[0].astype(f32)        # [1, Dk] replicated row
        bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)
        y = y + bonus * v

    # cross-chunk via carried state
    state = state_scr[...]
    y = y + jax.lax.dot_general(q_fac, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    decay_last = jnp.exp(jnp.maximum(cum[-1:, :], -CLAMP))        # [1, Dk]
    k_state = k * jnp.exp(jnp.maximum(cum[-1:, :] - cum, -CLAMP))  # [W, Dk]
    state_scr[...] = state * decay_last.T + jax.lax.dot_general(
        k_state, v, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _final():
        state_out_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "mode", "interpret"))
def linear_scan_chunked(r, k, v, log_w, u=None, *, chunk: int = 64,
                        mode: str = "inclusive", interpret: bool = False):
    """r,k: [BH, S, Dk]; v: [BH, S, Dv]; log_w broadcastable to r.

    Returns (y [BH, S, Dv], state [BH, Dk, Dv]).  Oracle:
    repro.models.linear_scan.chunked_scan (leading dims flattened).
    """
    BH, S, Dk = r.shape
    Dv = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    C = S // chunk
    lw = jnp.broadcast_to(log_w, r.shape).astype(jnp.float32)
    if u is None:
        u = jnp.zeros((BH, Dk), jnp.float32)
    u2 = u.reshape(BH, 1, Dk)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=C, mode=mode)
    y, state = pl.pallas_call(
        kernel,
        grid=(BH, C),
        in_specs=[
            pl.BlockSpec((1, chunk, Dk), lambda x, c: (x, c, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda x, c: (x, c, 0)),
            pl.BlockSpec((1, chunk, Dv), lambda x, c: (x, c, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda x, c: (x, c, 0)),
            pl.BlockSpec((1, 1, Dk), lambda x, c: (x, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, Dv), lambda x, c: (x, c, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda x, c: (x, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, Dv), v.dtype),
            jax.ShapeDtypeStruct((BH, Dk, Dv), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
        name="linear_scan_chunked",
    )(r, k, v, lw, u2)
    return y, state
