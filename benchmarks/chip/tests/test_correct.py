"""``correct`` at a size a test run holds (the cells' ``--rehearse`` sizes,
CPU, kernels in interpret mode): sound runs pass, the float8 control and
each fault a served cell can have are caught."""

import json

import jax.numpy as jnp
import pytest

import run
from harness import spec

CELLS = [w["name"] for w in spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]]
SEED = 2**31 + 99


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def rehearse(cell, seed=SEED):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds", "3",
                     "--trace", "0", "--rehearse"])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    assert rehearse(cell) == 0
    out = last_line(capsys)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["rehearsal"] is True and list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    """The reference in float8 in the program's place reads above the limit
    on the tokens the program served."""
    c = spec.load_cell(cell)
    run.init_jax(True, 1)
    S = run.setup(c, SEED, True)
    hub, results, reqs = run.serve_window(S, SEED, 3.0, None)
    fin = run.finished_requests(hub, results, reqs)
    limit = c.limits["mean_logit_gap"]
    program = run.logit_gaps(S, S.params, fin, SEED)[3]
    control = run.logit_gaps(S, S.params, fin, SEED, control=True)[3]
    assert program <= limit < control


def altered_token(monkeypatch):
    """A token altered where it is produced: the sampler's choice + 1."""
    import repro.serving.scheduler as sched
    orig = sched.sample
    monkeypatch.setattr(sched, "sample", lambda lg, *a, **k:
                        (orig(lg, *a, **k) + 1) % lg.shape[-1])


def state_unchanged(monkeypatch):
    """A decode step that returns its cache unchanged."""
    import repro.models.transformer as tfm
    orig = tfm.decode_tokens

    def frozen(cfg, params, tb, caches, *a, **k):
        return orig(cfg, params, tb, caches, *a, **k)[0], caches
    monkeypatch.setattr(tfm, "decode_tokens", frozen)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch, capsys):
    fault(monkeypatch)
    assert rehearse(cell) == 0
    out = last_line(capsys)
    assert out["correct"] is False
    c = out["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


def test_reference_matches_served_math_exactly_in_f32():
    """The ``dense_decoder`` reference's own arithmetic: on a 1-layer toy
    with identity norms, its logits equal a direct numpy forward."""
    import numpy as np

    from harness import arch
    rng = np.random.default_rng(0)
    d, H, Dh, F, V, T = 8, 2, 4, 16, 11, 5
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    p = {"embed": w(V, d), "final_norm": {"scale": jnp.zeros(d)},
         "blocks": ({"ln1": {"scale": jnp.zeros((1, d))},
                     "ln2": {"scale": jnp.zeros((1, d))},
                     "attn": {"wq": w(1, d, H * Dh), "wk": w(1, d, H * Dh),
                              "wv": w(1, d, H * Dh), "wo": w(1, H * Dh, d)},
                     "mlp": {"w_gate": w(1, d, F), "w_up": w(1, d, F),
                             "w_down": w(1, F, d)}},)}
    cfg = {"hidden_size": d, "num_attention_heads": H, "num_key_value_heads": H,
           "head_dim": Dh, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
           "hidden_act": "silu"}
    toks = np.array([1, 4, 2, 7, 3], np.int32)
    served = np.array([5, 6, 0], np.int32)     # predicted at positions 2..4
    g = arch.load("dense_decoder").gaps(cfg, p, toks[:3], served, pad_to=512)

    # numpy forward
    P = {k: np.asarray(v) for k, v in p["blocks"][0]["attn"].items()}
    M = {k: np.asarray(v) for k, v in p["blocks"][0]["mlp"].items()}
    E = np.asarray(p["embed"], np.float64)
    rms = lambda x: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)
    seq = np.concatenate([toks[:3], served[:-1]])
    x = E[seq]
    h = rms(x)
    q, k, v = (h @ P[n][0] for n in ("wq", "wk", "wv"))

    def rope(a):
        a = a.reshape(T, H, Dh)
        f = 1.0 / 10000.0 ** (np.arange(0, Dh, 2) / Dh)
        ang = np.arange(T)[:, None] * f
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a1, a2 = a[..., :Dh // 2], a[..., Dh // 2:]
        return np.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)
    q, k, v = rope(q), rope(k), v.reshape(T, H, Dh)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dh)
    s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", pr, v).reshape(T, H * Dh)
    x = x + o @ P["wo"][0]
    h = rms(x)
    gate = h @ M["w_gate"][0]
    x = x + (gate / (1 + np.exp(-gate)) * (h @ M["w_up"][0])) @ M["w_down"][0]
    logits = rms(x) @ E.T
    want = [logits[2 + i].max() - logits[2 + i, served[i]] for i in range(3)]
    np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-5)
