"""What every plain reference shares, and the comparison that decides
``correct``.

A configuration's architecture module (``archs/<arch_module>.py``) builds
its float32 forward in jax.numpy from these pieces, every matrix product at
``Precision.HIGHEST`` through ``_mm``, and hands ``gaps`` two functions: the
final hidden states of a padded sequence, and the output head over a block
of them.  A reference imports nothing of the served program and has no
cache, batching or kernel: the whole sequence is recomputed, one layer at a
time, queries in blocks of ``Q_BLOCK``.

Departures from the published configurations are in the configuration
files (``assumed``); the weights are the run's own (``harness.weights``),
read through the served layout, so an RMSNorm weight is ``1 + scale``.

``control`` computes the same forward with every matrix product's operands
rounded to float8 (e4m3) with a scale per row of the contraction, the next
precision below the configuration's bf16: the lower-precision step a later
change might take.  Its gaps must fail the limit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
P_BLOCK = 256          # logit positions per output-head call
F8_MAX = 448.0         # largest finite float8_e4m3fn


def _f8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction), and back to f32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, control: bool, a_axis: int, b_axis: int):
    if control:
        a, b = _f8(a, a_axis), _f8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, p, cfg: dict):
    if "bias" in p:        # LayerNorm
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])
                * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + cfg["norm_eps"]) * (
        1.0 + p["scale"].astype(jnp.float32))


def _rope(x, theta: float):
    """x: [T, H, Dh]; positions 0..T-1; rotation on the two halves."""
    T, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # [T, Dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gaps(hidden, logits, prompt: np.ndarray, served: np.ndarray,
         pad_to: int, control: bool = False) -> np.ndarray:
    """For each served token, how far the reference's logit of the token
    that was served lies below the reference's best, in logits.

    ``hidden(tokens, control)`` gives the final hidden states [pad_to, d] of
    a sequence; ``logits(h, control)`` the output head over ``P_BLOCK`` of
    them.  ``prompt`` [P] and ``served`` [n] (the tokens the program
    generated for it) are run through the reference once, as one sequence,
    padded at the end to ``pad_to`` (positions past the real ones never
    reach a real one: attention is causal).  With ``control``, the token
    judged at each position is the one the float8 forward puts first on the
    same input, and its gap is read under the float32 reference."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to {pad_to}")
    toks = jnp.asarray(np.pad(seq, (0, pad_to - seq.size)))
    pos = np.arange(prompt.size - 1, seq.size)          # predicts served[i]
    h = hidden(toks, False)
    hc = hidden(toks, True) if control else None
    out = []
    for lo in range(0, pos.size, P_BLOCK):
        p = np.zeros(P_BLOCK, np.int64)
        n = min(P_BLOCK, pos.size - lo)
        p[:n] = pos[lo:lo + n]
        ref = logits(h[p], False)
        if control:
            tok = jnp.argmax(logits(hc[p], True), -1)
        else:
            t = np.zeros(P_BLOCK, np.int64)
            t[:n] = served[lo:lo + n]
            tok = jnp.asarray(t)
        g = jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
        out.append(np.asarray(g)[:n])
    return np.concatenate(out)
