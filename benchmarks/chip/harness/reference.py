"""What every plain reference shares, and the comparison that decides
``correct``.

A configuration's architecture module (``archs/<arch_module>.py``) builds
its float32 forward in jax.numpy from these pieces (norms ``_norm``, rotary
embeddings ``_rope`` and ``_rope_yarn``, causal or sliding-window attention
``_attention``, a drop-free expert FFN ``_moe``), every matrix product at
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

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
P_BLOCK = 256          # logit positions per output-head call
MOE_BLOCK = 256        # token rows per expert-FFN block
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


def _yarn_range(dh: int, theta: float, original_max: int, beta_fast: float,
                beta_slow: float) -> tuple[int, float]:
    """YaRN's band of rotary dimensions (pairs) that move from plain RoPE
    to interpolated: the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over ``original_max`` positions (transformers'
    ``_compute_yarn_parameters``, truncated)."""
    corr = lambda r: dh * math.log(original_max / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dh - 1)
    if low == high:
        high += 0.001       # a ramp of width 0 would divide by 0
    return low, high


def _yarn_inv_freq(dh: int, theta: float, factor: float, original_max: int,
                   beta_fast: float, beta_slow: float):
    """[Dh/2] rotary frequencies: plain RoPE's below the band, ``1/factor``
    of them above it, a linear ramp between."""
    pos_freqs = theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    low, high = _yarn_range(dh, theta, original_max, beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)


def _rope_yarn(x, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float, attention_factor: float):
    """``_rope`` with YaRN's frequencies, cos and sin scaled by
    ``attention_factor``.  x: [T, H, Dh]; positions 0..T-1."""
    T, _, dh = x.shape
    freqs = _yarn_inv_freq(dh, theta, factor, original_max, beta_fast, beta_slow)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # [T, Dh/2]
    cos = (jnp.cos(ang) * attention_factor)[:, None]
    sin = (jnp.sin(ang) * attention_factor)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, control: bool, window: int = 0):
    """Causal softmax attention, scale ``Dh ** -0.5``, queries in blocks of
    ``Q_BLOCK``.  q: [T, H, Dh]; k, v: [T, Hkv, Dh], query head ``h`` on KV
    head ``h // (H / Hkv)``.  With ``window > 0`` the query at position p
    sees keys p - window + 1 .. p.  Returns [T, H, Dh]."""
    T, H, Dh = q.shape
    Hkv = k.shape[1]
    q = q.reshape(T, Hkv, H // Hkv, Dh)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = _mm("qhgd,khd->hgqk", qb, k, control, 3, 2) * Dh ** -0.5
        qpos, kpos = (lo + jnp.arange(qb.shape[0]))[:, None], jnp.arange(T)[None]
        seen = qpos >= kpos
        if window:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("hgqk,khd->qhgd", p, v, control, 3, 0))
    return jnp.concatenate(outs, 0).reshape(T, H, Dh)


def _moe(h, mp, n_experts: int, top_k: int, norm_topk: bool, control: bool):
    """A mixture of SwiGLU experts with no capacity: every token gets its
    ``top_k`` experts of a softmax router over all ``n_experts``, weighted
    by their router probabilities (renormalised to sum 1 with
    ``norm_topk``).  h: [T, d]; ``mp`` in the served tree's names:
    ``router [d, E]``, ``w_gate`` / ``w_up [E, d, f]``, ``w_down [E, f, d]``.

    Every expert runs on every token, ``MOE_BLOCK`` tokens at a time, and
    the router's weights, zero off a token's ``top_k``, mask the sum; no
    token's result depends on another's."""
    w = lambda a: a.astype(jnp.float32)
    outs = []
    for lo in range(0, h.shape[0], MOE_BLOCK):
        hb = h[lo:lo + MOE_BLOCK]
        probs = jax.nn.softmax(_mm("td,de->te", hb, w(mp["router"]), control, 1, 0), -1)
        top, idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top = top / jnp.sum(top, -1, keepdims=True)
        mask = jnp.sum(jax.nn.one_hot(idx, n_experts) * top[..., None], 1)   # [t, E]
        gate = _mm("td,edf->etf", hb, w(mp["w_gate"]), control, 1, 1)
        up = _mm("td,edf->etf", hb, w(mp["w_up"]), control, 1, 1)
        y = _mm("etf,efd->etd", jax.nn.silu(gate) * up, w(mp["w_down"]), control, 2, 1)
        outs.append(jnp.sum(mask.T[:, :, None] * y, 0))
    return jnp.concatenate(outs, 0)


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
