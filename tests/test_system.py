"""End-to-end behaviour tests: serving engine, scheduler, GEAR-vs-FP16 logit
fidelity, data pipeline determinism, checkpoint atomicity."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.policy import FP16, named_policy
from repro.data.synthetic import DataConfig, make_batch
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import Scheduler, Request
import repro.ckpt.checkpoint as ck


@pytest.fixture(scope="module")
def dense_model():
    cfg = smoke_config("minicpm-2b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _serve(eng, prompts, max_new_tokens: int) -> np.ndarray:
    """Serve each prompt row as one request through continuous batching;
    the generated tokens, one row per prompt."""
    sched = Scheduler(eng)
    for rid, toks in enumerate(np.asarray(prompts)):
        sched.submit(Request(rid=rid, tokens=toks,
                             max_new_tokens=max_new_tokens))
    res = sorted(sched.run_continuous(), key=lambda r: r.rid)
    return np.stack([r.tokens for r in res])


def test_generate_shapes_and_determinism(dense_model):
    cfg, m, params = dense_model
    pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=16)
    eng = Engine(m, params, EngineConfig(batch=2, capacity=96, policy=pol))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size)
    t1 = _serve(eng, prompts, 12)
    t2 = _serve(eng, prompts, 12)
    assert t1.shape == (2, 12)
    assert (t1 == t2).all()  # greedy decode is deterministic


def test_gear_vs_fp16_generation_close(dense_model):
    """4-bit GEAR generation tracks FP16 generation for many steps —
    the error-compounding claim (paper Fig 1b) at small scale."""
    cfg, m, params = dense_model
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, cfg.vocab_size)
    pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=16)
    eng_g = Engine(m, params, EngineConfig(batch=2, capacity=96, policy=pol))
    eng_f = Engine(m, params, EngineConfig(batch=2, capacity=96, policy=FP16))
    tg = _serve(eng_g, prompts, 10)
    tf = _serve(eng_f, prompts, 10)
    agree = float((tg == tf).mean())
    assert agree >= 0.5, f"4-bit GEAR diverged too fast: agreement {agree}"


def test_gear_cache_smaller_than_fp16(dense_model):
    cfg, m, params = dense_model
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, cfg.vocab_size)
    pol = dataclasses.replace(named_policy("gear_kivi2"), buffer_size=16, group=16)
    nbytes = {}
    for name, p in (("gear", pol), ("fp16", FP16)):
        eng = Engine(m, params, EngineConfig(batch=2, capacity=96, policy=p))
        view = eng.new_view()          # the live tree a scheduler serves from
        for slot, toks in enumerate(np.asarray(prompts)):
            view.prefill_slot({"tokens": toks[None]}, slot)
        view.decode({"tokens": jnp.zeros((2, 1), jnp.int32)},
                    np.full(2, prompts.shape[1], np.int32))
        nbytes[name] = Engine.cache_nbytes(view.caches)
    # packed int32 carriers count 4 bytes; the bit-level size is what the
    # metrics module reports — structural check only here.
    assert nbytes["gear"] < nbytes["fp16"]


def test_scheduler_drains_queue(dense_model):
    cfg, m, params = dense_model
    pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=16)
    eng = Engine(m, params, EngineConfig(batch=2, capacity=96, policy=pol))
    sched = Scheduler(eng)
    for i in range(3):
        sched.submit(Request(rid=i, tokens=np.arange(5 + i) % cfg.vocab_size,
                             max_new_tokens=6))
    res = sched.run_continuous()
    assert sorted(r.rid for r in res) == [0, 1, 2]
    assert all(r.tokens.shape == (6,) for r in res)


def test_data_pipeline_deterministic():
    cfg = smoke_config("minicpm-2b")
    dc = DataConfig(seed=7, vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    b1 = make_batch(dc, cfg, 5)
    b2 = make_batch(dc, cfg, 5)
    b3 = make_batch(dc, cfg, 6)
    assert (b1["tokens"] == b2["tokens"]).all()
    assert not (b1["tokens"] == b3["tokens"]).all()
    assert int(b1["tokens"].max()) < cfg.vocab_size


def test_checkpoint_atomic_and_corruption_detected(tmp_path):
    tree = {"a": jnp.arange(10), "b": {"c": jnp.ones((3, 3))}}
    d = ck.save(str(tmp_path), 1, tree)
    assert os.path.exists(os.path.join(d, "_COMMITTED"))
    assert ck.latest_step(str(tmp_path)) == 1
    restored = ck.restore(str(tmp_path), 1, tree)
    assert (restored["a"] == tree["a"]).all()
    # corrupt a leaf -> restore must fail loudly
    np.save(os.path.join(d, "arr_0.npy"), np.arange(10) + 1)
    with pytest.raises(IOError):
        ck.restore(str(tmp_path), 1, tree)


def test_uncommitted_checkpoint_ignored(tmp_path):
    tree = {"a": jnp.arange(4)}
    d = ck.save(str(tmp_path), 3, tree)
    os.remove(os.path.join(d, "_COMMITTED"))
    assert ck.latest_step(str(tmp_path)) is None
