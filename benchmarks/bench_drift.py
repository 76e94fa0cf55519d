"""Paper Fig 1b / Table 1 proxy: logit drift + generation agreement along
decode steps, on a small model briefly trained on structured synthetic data.

The paper's core qualitative claim: plain low-bit quantization compounds
approximation error across autoregressive steps and diverges from the FP16
trajectory; GEAR stays near-lossless.  We measure (a) max |Δlogit| vs FP16
per decode step, (b) token-level agreement of greedy generations.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs import smoke_config
from repro.core.policy import FP16, named_policy
from repro.data.synthetic import DataConfig
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineConfig
from repro.train.loop import train_loop
from repro.train.state import RunConfig
import tempfile


def trained_small_model(steps: int = 40):
    cfg = dataclasses.replace(smoke_config("llama2-7b"), vocab_size=256)
    model = build_model(cfg)
    run = RunConfig(total_steps=steps, warmup_steps=5, microbatches=1, remat=False,
                    zero1=False, ckpt_dir=tempfile.mkdtemp(), ckpt_every=0,
                    log_every=10**9)
    dc = DataConfig(seed=3, vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    state = train_loop(model, jax.make_mesh((1, 1), ("data", "model")), run, dc,
                       log_fn=lambda *_: None)
    return cfg, model, jax.device_get(state.params)


def drift_curves(cfg, model, params, policies: dict, gen: int = 24, prompt: int = 40):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (4, prompt), 0,
                                          cfg.vocab_size)}
    base_tokens, base_logits = _rollout(cfg, model, params, batch, FP16, gen)
    out = {}
    for name, pol in policies.items():
        toks, logits = _rollout(cfg, model, params, batch, pol, gen)
        drift = jnp.abs(logits - base_logits).max(axis=(0, 2))     # per step
        agree = (toks == base_tokens).mean()
        out[name] = {"drift": drift, "agreement": float(agree)}
    return out


def _rollout(cfg, model, params, batch, policy, gen):
    eng = Engine(model, params, EngineConfig(batch=batch["tokens"].shape[0],
                                             capacity=128, policy=policy))
    logits, caches = model.prefill(params, batch, policy, eng._cap())
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    toks, logit_list = [tok], [logits[:, -1]]
    pos = batch["tokens"].shape[1]
    for t in range(gen - 1):
        logits, caches = eng.decode({"tokens": tok[:, None]}, caches, pos + t)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        toks.append(tok)
        logit_list.append(logits[:, -1])
    return jnp.stack(toks, 1), jnp.stack(logit_list, 1)  # [B,T], [B,T,V]


def run():
    cfg, model, params = trained_small_model()
    nb16 = lambda n: dataclasses.replace(named_policy(n), buffer_size=16,
                                         group=min(16, named_policy(n).group))
    policies = {
        "per_token_q2": nb16("per_token_q2"),
        "kivi2": nb16("kivi2"),
        "gear_l_kivi2": nb16("gear_l_kivi2"),
        "gear_kivi2": nb16("gear_kivi2"),
        "gear_kcvt4": nb16("gear_kcvt4"),
    }
    res = drift_curves(cfg, model, params, policies)
    for name, r in res.items():
        d = r["drift"]
        emit(f"fig1b_drift/{name}", 0.0,
             f"agree={r['agreement']:.2f} drift_first={float(d[0]):.3f} "
             f"drift_last={float(d[-1]):.3f}")
    # GEAR tracks FP16 better than its own quant backbone
    assert res["gear_kivi2"]["agreement"] >= res["kivi2"]["agreement"]
    return res


if __name__ == "__main__":
    run()
