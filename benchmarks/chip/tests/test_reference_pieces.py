"""The reference's pieces for a windowed mixture-of-experts decoder
(``harness.reference``): YaRN at Mellum2-12B-A2.5B's published values, the
sliding-window mask, the drop-free expert FFN, and the pieces assembled
into a decoder against the program's own forward."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference as R
from harness import weights

# Mellum2's full-attention RoPE (published config.json, rope_parameters.full_attention)
DH = 128
YARN = dict(theta=500000.0, factor=16.0, original_max=8192, beta_fast=32.0, beta_slow=1.0)
ATTENTION_FACTOR = 1.2772588722239782


def normal(rng, *shape, std=1.0):
    return jnp.asarray(rng.normal(size=shape) * std, jnp.float32)


# -- YaRN

def test_yarn_band_at_mellum2():
    corr = lambda r: DH * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000.0))
    assert (round(corr(32), 2), round(corr(1), 2)) == (18.08, 34.98)
    assert R._yarn_range(DH, 500000.0, 8192, 32.0, 1.0) == (18, 35)


def test_yarn_frequencies_at_mellum2():
    plain = np.asarray(1.0 / 500000.0 ** (jnp.arange(0, DH, 2, dtype=jnp.float32) / DH))
    inv = np.asarray(R._yarn_inv_freq(DH, **YARN))
    assert inv.shape == (64,)
    np.testing.assert_array_equal(inv[:18], plain[:18])          # below the band
    np.testing.assert_array_equal(inv[35:], plain[35:] / 16)     # above it
    ramp = (inv[18:35] - plain[18:35]) / (plain[18:35] / 16 - plain[18:35])
    np.testing.assert_allclose(ramp, np.arange(17) / 17, atol=1e-5)


def test_rope_yarn_scales_by_the_attention_factor():
    x = normal(np.random.default_rng(0), 6, 2, DH)
    y = R._rope_yarn(x, **YARN, attention_factor=ATTENTION_FACTOR)
    np.testing.assert_array_equal(y[0], x[0] * ATTENTION_FACTOR)   # position 0
    # a rotation of each (i, i + Dh/2) pair, scaled
    pair = lambda a: jnp.hypot(a[..., :DH // 2], a[..., DH // 2:])
    np.testing.assert_allclose(pair(y), pair(x) * ATTENTION_FACTOR, rtol=1e-5)
    # factor 1 and no scaling: plain RoPE
    plain = R._rope_yarn(x, 500000.0, 1.0, 8192, 32.0, 1.0, 1.0)
    np.testing.assert_allclose(plain, R._rope(x, 500000.0), rtol=1e-5, atol=1e-6)


# -- sliding-window attention

T_ATTN = R.Q_BLOCK + 37          # two query blocks


def qkv(seed=1):
    rng = np.random.default_rng(seed)
    return (normal(rng, T_ATTN, 4, 8), normal(rng, T_ATTN, 2, 8), normal(rng, T_ATTN, 2, 8))


def attention_by_query(q, k, v, window):
    """One query at a time in float64: softmax over keys max(0, p - window
    + 1) .. p (0 .. p without a window); query head h on KV head h // 2."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    T, H, Dh = q.shape
    out = np.zeros_like(q)
    for p in range(T):
        lo = max(0, p - window + 1) if window else 0
        for h in range(H):
            s = k[lo:p + 1, h // 2] @ q[p, h] / math.sqrt(Dh)
            e = np.exp(s - s.max())
            out[p, h] = (e / e.sum()) @ v[lo:p + 1, h // 2]
    return out


@pytest.mark.parametrize("window", [0, 1, 8, 1024])
def test_attention_equals_a_loop_over_queries(window):
    q, k, v = qkv()
    np.testing.assert_allclose(R._attention(q, k, v, False, window),
                               attention_by_query(q, k, v, window), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("window", [T_ATTN, T_ATTN + 1, 4 * T_ATTN])
def test_a_window_past_the_sequence_is_full_attention(window):
    q, k, v = qkv(2)
    np.testing.assert_array_equal(R._attention(q, k, v, False, window),
                                  R._attention(q, k, v, False))


# -- the expert FFN

E, TOP_K, D, F = 8, 2, 16, 8
T_MOE = R.MOE_BLOCK + 44         # two token blocks


def experts(seed=3):
    rng = np.random.default_rng(seed)
    mp = {"router": normal(rng, D, E, std=D ** -0.5),
          "w_gate": normal(rng, E, D, F, std=D ** -0.5),
          "w_up": normal(rng, E, D, F, std=D ** -0.5),
          "w_down": normal(rng, E, F, D, std=F ** -0.5)}
    return normal(rng, T_MOE, D), mp


def moe_by_token(h, mp, norm_topk):
    """One token at a time in float64, over its own top-k experts only."""
    h = np.asarray(h, np.float64)
    m = {k: np.asarray(v, np.float64) for k, v in mp.items()}
    out = np.zeros_like(h)
    for t, x in enumerate(h):
        lg = x @ m["router"]
        probs = np.exp(lg - lg.max()) / np.exp(lg - lg.max()).sum()
        chosen = np.argsort(-probs)[:TOP_K]
        w = probs[chosen] / (probs[chosen].sum() if norm_topk else 1.0)
        for e, we in zip(chosen, w):
            g = x @ m["w_gate"][e]
            out[t] += we * ((g / (1 + np.exp(-g)) * (x @ m["w_up"][e])) @ m["w_down"][e])
    return out


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_equals_a_loop_over_each_tokens_experts(norm_topk):
    h, mp = experts()
    np.testing.assert_allclose(R._moe(h, mp, E, TOP_K, norm_topk, False),
                               moe_by_token(h, mp, norm_topk), rtol=1e-5, atol=1e-6)


def test_moe_token_does_not_depend_on_its_batch_mates():
    h, mp = experts()
    batch = R._moe(h, mp, E, TOP_K, True, False)
    others = h.at[:].set(normal(np.random.default_rng(4), T_MOE, D))
    for t in (0, 1, R.MOE_BLOCK - 1, R.MOE_BLOCK, T_MOE - 1):
        # other batch-mates, same shapes: bit for bit
        mates = R._moe(others.at[t].set(h[t]), mp, E, TOP_K, True, False)
        np.testing.assert_array_equal(mates[t], batch[t])
        # alone: a one-row product sums in another order on the CPU, so to
        # float32 rounding of sums of 16 and 8 terms
        alone = R._moe(h[t:t + 1], mp, E, TOP_K, True, False)
        np.testing.assert_allclose(alone[0], batch[t], rtol=1e-5, atol=1e-7)


# -- the pieces as a decoder, against the program's own forward

WINDOW, X_E, X_K, X_T = 8, 8, 2, 48
MEDIAN_BOUND = 0.4


def windowed_moe_config():
    """Two periods of 3 window layers (window 8) then 1 full layer (RoPE
    theta 500,000), each with 8 SwiGLU experts, 2 a token; capacity E / k
    drops no token."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name="windowed-moe", family="moe", num_layers=8, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512, mlp_kind="swiglu",
        norm="rmsnorm", rope_theta=500000.0, attn_pattern="local_global",
        local_window=WINDOW, pattern_locals=3, moe=True, num_experts=X_E,
        moe_top_k=X_K, moe_d_ff=32, capacity_factor=X_E / X_K,
        tie_embeddings=False, max_seq_len=512)


@jax.jit(static_argnames=("thetas", "window", "top_k"))
def decoder_logits(params, tokens, thetas, window=WINDOW, top_k=X_K):
    """The float32 reference of ``windowed_moe_config``, from the pieces;
    ``thetas``: the RoPE theta of the window layers and of the full ones."""
    f = lambda a: a.astype(jnp.float32)
    cfg = {"norm_eps": 1e-6}
    T, H, Hkv, Dh = tokens.shape[0], 4, 2, 16
    x = f(params["embed"][tokens])
    for r in range(2):
        for i, kind in enumerate(("window",) * 3 + ("full",)):
            lp = jax.tree.map(lambda a: a[r], params["blocks"][i])
            a = lp["attn"]
            h = R._norm(x, lp["ln1"], cfg)
            q = R._mm("td,de->te", h, f(a["wq"]), False, 1, 0).reshape(T, H, Dh)
            k = R._mm("td,de->te", h, f(a["wk"]), False, 1, 0).reshape(T, Hkv, Dh)
            v = R._mm("td,de->te", h, f(a["wv"]), False, 1, 0).reshape(T, Hkv, Dh)
            theta = thetas[kind == "full"]
            o = R._attention(R._rope(q, theta), R._rope(k, theta), v, False,
                             window if kind == "window" else 0)
            x = x + R._mm("te,ed->td", o.reshape(T, H * Dh), f(a["wo"]), False, 1, 0)
            x = x + R._moe(R._norm(x, lp["ln2"], cfg), lp["moe"], X_E, top_k, True, False)
    return R._mm("td,dv->tv", R._norm(x, params["final_norm"], cfg),
                 f(params["lm_head"]), False, 1, 0)


@pytest.mark.parametrize("case, departure", [
    ("as served", {}), ("window 9", {"window": 9}), ("window 7", {"window": 7}),
    ("top-3", {"top_k": 3}), ("window theta x50", {"window_theta_x": 50.0})])
def test_pieces_match_the_programs_windowed_moe_forward(case, departure):
    """The program's ``forward(mode="train")`` (bf16 activations) against the
    float32 reference, logits at every position of a 48-token sequence.

    The RoPE theta of each layer kind is the program's own choice (today
    10,000 on window layers whatever ``rope_theta`` says), so it is read
    from the program (``rope_theta_for``) and given to the reference as an
    input; the reference's mathematics is its own.

    Bound: the median over positions of the largest logit difference, as a
    share of the reference logits' rms, at most 0.4.  bf16 activations put
    the router's inputs about 1% off the reference's, and a token whose 2nd
    and 3rd experts lie closer than that takes the other one: its logits
    move by up to 2.2x rms, and through attention those after it.  So the
    bound holds the median position, not the worst.  On 24 seeds the served
    math read 0.03-0.21; each departure here read 0.93 or more (top-3 the
    nearest), a window off by one 1.26 or more, and window layers at 50
    times their theta 1.03."""
    from repro.models.attention import rope_theta_for
    from repro.models.model import build_model
    from repro.models.transformer import forward
    mcfg = windowed_moe_config()
    params = weights.make_weights(build_model(mcfg).init_abstract(), 2**31 + 1001)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (X_T,), 0, mcfg.vocab_size)
    program = forward(mcfg, params, {"tokens": tokens[None]}, mode="train")[0][0]
    thetas = (rope_theta_for(mcfg, "local"), rope_theta_for(mcfg, "global"))
    want = decoder_logits(params, tokens, thetas)
    departure = dict(departure)
    scale = departure.pop("window_theta_x", 1.0)
    got = decoder_logits(params, tokens, (thetas[0] * scale, thetas[1]), **departure)
    rms = float(jnp.sqrt(jnp.mean(want ** 2)))
    median = float(jnp.median(jnp.max(jnp.abs(got - program.astype(jnp.float32)), -1))) / rms
    assert (median <= MEDIAN_BOUND) == (case == "as served"), median
