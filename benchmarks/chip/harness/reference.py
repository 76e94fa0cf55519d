"""Plain reference of the served decoder, and the comparison that decides
``correct``.

The reference follows the published architecture in float32 jax.numpy,
every matrix product at ``Precision.HIGHEST``: token embedding, then per
layer a pre-norm attention block (rotary embedding on the two halves of each
head, causal softmax attention with grouped KV heads, scale ``head_dim **
-0.5``) and a pre-norm MLP (``silu`` gated, or ``gelu`` with the tanh
approximation), a final norm and the tied (or separate) output head.  It
imports nothing of the served program and has no cache, batching or kernel:
the whole sequence is recomputed, one layer at a time, queries in blocks.

Departures from the published configurations are in the configuration
files (``assumed``); the weights are the run's own (``harness.weights``),
read through the served layout, so an RMSNorm weight is ``1 + scale``.

``control`` computes the same forward with every matrix product's operands
rounded to float8 (e4m3) with a scale per row of the contraction, the next
precision below the configuration's bf16: the lower-precision step a later
change might take.  Its gaps must fail the limit.
"""

from __future__ import annotations

import functools

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


def _layer(cfg: dict, control: bool, x, lp):
    T, d = x.shape
    H, Hkv, Dh = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    G = H // Hkv
    w = lambda a: a.astype(jnp.float32)
    a = lp["attn"]
    h = _norm(x, lp["ln1"], cfg)
    q = _mm("td,de->te", h, w(a["wq"]), control, 1, 0).reshape(T, H, Dh)
    k = _mm("td,de->te", h, w(a["wk"]), control, 1, 0).reshape(T, Hkv, Dh)
    v = _mm("td,de->te", h, w(a["wv"]), control, 1, 0).reshape(T, Hkv, Dh)
    q = _rope(q, cfg["rope_theta"]).reshape(T, Hkv, G, Dh)
    k = _rope(k, cfg["rope_theta"])
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = _mm("qhgd,khd->hgqk", qb, k, control, 3, 2) * Dh ** -0.5
        causal = (lo + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("hgqk,khd->qhgd", p, v, control, 3, 0))
    o = jnp.concatenate(outs, 0).reshape(T, H * Dh)
    x = x + _mm("te,ed->td", o, w(a["wo"]), control, 1, 0)
    h = _norm(x, lp["ln2"], cfg)
    m = lp["mlp"]
    up = _mm("td,df->tf", h, w(m["w_up"]), control, 1, 0)
    if cfg["gated"]:
        up = jax.nn.silu(_mm("td,df->tf", h, w(m["w_gate"]), control, 1, 0)) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + _mm("tf,fd->td", up, w(m["w_down"]), control, 1, 0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _hidden(params, tokens, cfg_items, control):
    cfg = dict(cfg_items)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    (blocks,) = params["blocks"]
    x, _ = jax.lax.scan(lambda c, lp: (_layer(cfg, control, c, lp), None),
                        x, blocks)
    return _norm(x, params["final_norm"], cfg)


@functools.partial(jax.jit, static_argnames=("control",))
def _logits(params, h, control):
    if "lm_head" in params:
        return _mm("pd,dv->pv", h, params["lm_head"].astype(jnp.float32),
                   control, 1, 0)
    return _mm("pd,vd->pv", h, params["embed"].astype(jnp.float32),
               control, 1, 1)


def ref_config(cfg: dict) -> dict:
    """The reference's view of a configuration file."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    act = cfg["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"reference has no activation {act!r}")
    return {"heads": H, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim", d // H),
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))),
            "gated": act == "silu"}


def gaps(cfg: dict, params, prompt: np.ndarray, served: np.ndarray,
         pad_to: int, control: bool = False) -> np.ndarray:
    """For each served token, how far the reference's logit of the token
    that was served lies below the reference's best, in logits.

    ``prompt`` [P] and ``served`` [n] (the tokens the program generated for
    it) are run through the reference once, as one sequence, padded at the
    end to ``pad_to`` (positions past the real ones never reach a real one:
    attention is causal).  With ``control``, the token judged at each
    position is the one the float8 forward puts first on the same input,
    and its gap is read under the float32 reference."""
    items = tuple(sorted(ref_config(cfg).items()))
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to {pad_to}")
    toks = jnp.asarray(np.pad(seq, (0, pad_to - seq.size)))
    pos = np.arange(prompt.size - 1, seq.size)          # predicts served[i]
    h = _hidden(params, toks, items, False)
    hc = _hidden(params, toks, items, True) if control else None
    out = []
    for lo in range(0, pos.size, P_BLOCK):
        p = np.zeros(P_BLOCK, np.int64)
        n = min(P_BLOCK, pos.size - lo)
        p[:n] = pos[lo:lo + n]
        ref = _logits(params, h[p], False)
        if control:
            tok = jnp.argmax(_logits(params, hc[p], True), -1)
        else:
            t = np.zeros(P_BLOCK, np.int64)
            t[:n] = served[lo:lo + n]
            tok = jnp.asarray(t)
        g = jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
        out.append(np.asarray(g)[:n])
    return np.concatenate(out)
