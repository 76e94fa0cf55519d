"""A dense decoder with one layer kind: every layer a pre-norm attention
block (rotary embedding on the two halves of each head, causal softmax
attention with grouped KV heads, scale ``head_dim ** -0.5``) and a pre-norm
MLP (``silu`` gated, or ``gelu`` with the tanh approximation), a final norm
and the tied (or separate) output head.  Every layer keeps its K/V in the
GEAR pool.

The architecture module of ``arch_module: "dense_decoder"``
(``harness/arch.py`` says what a module gives); the reference is built from
``harness.reference``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops, reference
from harness.reference import _attention, _mm, _norm, _rope


def model_config(cfg: dict):
    """The served program's configuration from a configuration file."""
    from repro.configs import get_config
    p = cfg["program"]
    base = get_config(p["arch"])
    H = cfg["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=H, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // H),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        mlp_kind={"silu": "swiglu", "gelu_pytorch_tanh": "gelu_mlp"}[cfg["hidden_act"]],
        norm=p["norm"], max_seq_len=cfg["max_position_embeddings"])


def flop_model(cfg: dict) -> flops.Dense:
    """Model FLOPs; every layer is a GEAR layer (``gear_layers == layers``)."""
    return flops.Dense.from_config(cfg)


def _layer(cfg: dict, control: bool, x, lp):
    T, d = x.shape
    H, Hkv, Dh = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    w = lambda a: a.astype(jnp.float32)
    a = lp["attn"]
    h = _norm(x, lp["ln1"], cfg)
    q = _mm("td,de->te", h, w(a["wq"]), control, 1, 0).reshape(T, H, Dh)
    k = _mm("td,de->te", h, w(a["wk"]), control, 1, 0).reshape(T, Hkv, Dh)
    v = _mm("td,de->te", h, w(a["wv"]), control, 1, 0).reshape(T, Hkv, Dh)
    q = _rope(q, cfg["rope_theta"])
    k = _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, control).reshape(T, H * Dh)
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
    """``harness.reference.gaps`` through this architecture's forward."""
    items = tuple(sorted(ref_config(cfg).items()))
    return reference.gaps(lambda toks, c: _hidden(params, toks, items, c),
                          lambda h, c: _logits(params, h, c),
                          prompt, served, pad_to, control)
