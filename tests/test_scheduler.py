"""Slot-level continuous batching: splice isolation, agreement with the bare
model's greedy loop, EOS truncation, cache byte accounting, and pspec
legality.

The archetype test is splice isolation: a request spliced into a live batch
mid-decode must produce bit-identical greedy tokens to running it alone —
for every cache kind (gear / fp16 / window).  This pins the per-slot cache
layout, per-slot RoPE, and batch-invariant compression all at once.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.cache import CacheConfig
from repro.core.outlier import outlier_count
from repro.core.policy import FP16, named_policy
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import Request, Scheduler

EOS = 3
PROMPT_PAD = 8
GEAR_POL = dataclasses.replace(named_policy("gear_kcvt4"),
                               buffer_size=8, rank=2, rank_decode=2)

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                   num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                   vocab_size=64)
# local+global pattern -> one sliding-window (ring) cache and one full cache
TINY_WIN = dataclasses.replace(TINY, attn_pattern="local_global",
                               pattern_locals=1, local_window=8)

KINDS = {
    "gear": (TINY, GEAR_POL),
    "fp16": (TINY, FP16),
    "window": (TINY_WIN, FP16),
}

_ENGINES: dict = {}


def _engines(kind):
    """(batched engine, solo engine) pair per cache kind, built once."""
    if kind not in _ENGINES:
        cfg, pol = KINDS[kind]
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        ecfg = EngineConfig(batch=3, capacity=48, policy=pol, eos_id=EOS)
        _ENGINES[kind] = (Engine(model, params, ecfg),
                         Engine(model, params, dataclasses.replace(ecfg, batch=1)))
    return _ENGINES[kind]


def _requests(n=6, seed=0, length=None):
    rng = np.random.RandomState(seed)
    budgets = [6, 3, 9, 1, 5, 7, 2, 8][:n]
    return [Request(rid=i,
                    tokens=rng.randint(4, 64,
                                       size=length or rng.randint(2, PROMPT_PAD + 1)),
                    max_new_tokens=b)
            for i, b in enumerate(budgets)]


def _solo_reference(solo: Engine, req: Request) -> np.ndarray:
    """The request run alone through a batch-1 scheduler: the same raw-length
    prefill path (including any engine-side length bucketing) as the batched
    run, with no other slot live."""
    sched = Scheduler(solo)
    sched.submit(Request(rid=0, tokens=req.tokens,
                         max_new_tokens=req.max_new_tokens))
    (res,) = sched.run_continuous()
    return res.tokens


# ---------------------------------------------------------------------------
# Splice isolation (the archetype)


@pytest.mark.parametrize("kind", ["gear", "fp16", "window"])
def test_splice_isolation_bit_identical(kind):
    """Continuous-batched greedy output == solo output, token for token."""
    eng, solo = _engines(kind)
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    out = {r.rid: r.tokens for r in sched.run_continuous()}
    assert sorted(out) == [r.rid for r in reqs]
    for r in reqs:
        ref = _solo_reference(solo, r)
        np.testing.assert_array_equal(
            out[r.rid], ref,
            err_msg=f"{kind}: rid {r.rid} diverged from its solo run")


def _model_greedy(eng: Engine, req: Request) -> np.ndarray:
    """The request decoded by a plain batch-1 greedy loop over the bare
    model (``Model.prefill`` then ``Model.decode_step``, argmax each step),
    cut at its first EOS: no engine, slot, splice or scheduler involved."""
    model, pol, cap = eng.model, eng.ecfg.policy, eng._cap()
    prefill = jax.jit(lambda p, b: model.prefill(p, b, pol, cap))
    step = jax.jit(lambda p, b, c, pos: model.decode_step(p, b, c, pos, pol,
                                                          cap))
    logits, caches = prefill(eng.params,
                             {"tokens": jnp.asarray(req.tokens[None],
                                                    jnp.int32)})
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(req.tokens)
    while len(toks) < req.max_new_tokens and toks[-1] != EOS:
        logits, caches = step(eng.params,
                              {"tokens": jnp.asarray([[toks[-1]]], jnp.int32)},
                              caches, jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return np.asarray(toks, np.int32)


@pytest.mark.parametrize("kind", ["gear", "fp16", "window"])
def test_continuous_matches_model_greedy(kind):
    """The batch-3 engine's continuous-batched tokens == the bare model's
    batch-1 greedy loop, request by request: the slot machinery (splice,
    per-slot positions, reset, EOS bookkeeping) adds nothing to what the
    model computes."""
    eng, _ = _engines(kind)
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    out = {r.rid: r.tokens for r in sched.run_continuous()}
    for r in reqs:
        np.testing.assert_array_equal(
            out[r.rid], _model_greedy(eng, r),
            err_msg=f"{kind}: rid {r.rid} diverged from the model's greedy loop")


def test_continuous_per_request_latency_and_budgets():
    eng, _ = _engines("gear")
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    results = sched.run_continuous()
    budgets = {r.rid: r.max_new_tokens for r in reqs}
    for res in results:
        assert 1 <= len(res.tokens) <= budgets[res.rid]
        assert res.prefill_s >= 0 and res.decode_s >= 0
        if len(res.tokens) < budgets[res.rid]:       # ended early => own EOS
            assert res.tokens[-1] == EOS
        assert EOS not in res.tokens[:-1]            # nothing past first EOS
    assert sched.last_stats["decode_steps"] > 0


@pytest.mark.kernel
def test_splice_isolation_through_interpret_kernel():
    """Continuous batching with the REAL Pallas kernel (interpret mode on
    CPU): mixed-length batches dispatch the ragged fused path end to end and
    every request still matches its solo run token for token."""
    cfg, pol = KINDS["gear"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(batch=3, capacity=48, policy=pol, eos_id=EOS,
                        fused="interpret")
    eng = Engine(model, params, ecfg)
    solo = Engine(model, params, dataclasses.replace(ecfg, batch=1))
    sched = Scheduler(eng)
    reqs = _requests(4)
    for r in reqs:
        sched.submit(r)
    out = {r.rid: r.tokens for r in sched.run_continuous()}
    assert sched.last_stats["attend_path"] == "fused-interpret"
    for r in reqs:
        np.testing.assert_array_equal(
            out[r.rid], _solo_reference(solo, r),
            err_msg=f"interpret-kernel rid {r.rid} diverged from its solo run")


def test_decode_dispatches_fused_gear_attend(monkeypatch):
    """The engine's decode program routes GEAR layers through gear_attend
    (the fused path) — including for mixed-length position vectors — and
    fp16 engines stay on the jnp attend path."""
    from repro.kernels import ops as kernel_ops

    calls = []
    real = kernel_ops.gear_attend

    def spy(*a, **kw):
        calls.append(kw.get("force_kernel", False))
        return real(*a, **kw)

    monkeypatch.setattr(kernel_ops, "gear_attend", spy)
    cfg, pol = KINDS["gear"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, EngineConfig(batch=2, capacity=48, policy=pol))
    # fused="auto" names what runs: the jnp oracle off the TPU
    assert eng.attend_path == ("fused-kernel" if kernel_ops.on_tpu()
                               else "fused-oracle")
    caches = eng.init_caches()
    tb = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    eng.decode(tb, caches, jnp.asarray([5, 17], jnp.int32))   # mixed lengths
    assert calls, "decode trace never reached gear_attend"
    assert not any(calls)                                     # real kernel path, not forced

    calls.clear()
    fcfg, fpol = KINDS["fp16"]
    feng = Engine(build_model(fcfg), build_model(fcfg).init(jax.random.PRNGKey(0)),
                  EngineConfig(batch=2, capacity=48, policy=fpol))
    assert feng.attend_path == "xla"
    feng.decode(tb, feng.init_caches(), jnp.asarray([0, 0], jnp.int32))
    assert not calls


# ---------------------------------------------------------------------------
# EOS truncation


def test_wave_results_truncated_at_own_eos():
    """Each request's result is its uninterrupted token stream cut at its
    own first EOS (kept), under continuous batching.  An engine that never
    stops early (``eos_id=-1``) serves the full streams; the stop token is
    then taken from inside one of them, so at least one request really
    ends early."""
    eng, _ = _engines("gear")

    def serve(eos_id):
        e = Engine(eng.model, eng.params,
                   dataclasses.replace(eng.ecfg, eos_id=eos_id))
        sched = Scheduler(e)
        for r in _requests():
            sched.submit(r)
        return {res.rid: res.tokens for res in sched.run_continuous()}

    full = serve(-1)
    longest = max(full, key=lambda rid: len(full[rid]))
    stop = int(full[longest][2])
    cut = serve(stop)
    hit = 0
    for rid, stream in full.items():
        at = np.nonzero(stream == stop)[0]
        want = stream[: at[0] + 1] if at.size else stream
        hit += int(len(want) < len(stream))
        np.testing.assert_array_equal(cut[rid], want, err_msg=f"rid {rid}")
        assert stop not in cut[rid][:-1], (
            f"rid {rid} kept tokens after its own EOS")
    assert hit, "no request ended early at EOS: the truncation went untested"


# ---------------------------------------------------------------------------
# Cache byte accounting (pins the compression-ratio claim)


def _expected_gear_layer_bytes(ccfg: CacheConfig) -> int:
    """Closed-form byte count of one GEAR (kcvt) layer cache."""
    B, H, Dh, S = ccfg.batch, ccfg.kv_heads, ccfg.head_dim, ccfg.capacity
    pol = ccfg.policy
    per = 32 // pol.bits
    C, nb, r = ccfg.n_chunks, ccfg.chunk, pol.rank
    total = 2 * B * H * S * (Dh // per) * 4              # packed K+V codes
    total += 2 * B * H * C * Dh * 2                      # K scale+zero (per-channel)
    total += 2 * B * H * S * 1 * 2                       # V scale+zero (per-token)
    total += 2 * (B * H * S * r + B * H * C * Dh * r) * 2  # low-rank A + B, K+V
    ks = outlier_count(nb, pol.sparsity)                 # K outliers per chunk col
    kv = outlier_count(Dh, pol.sparsity)                 # V outliers per token row
    total += (B * H * C * Dh * 2 * ks + B * H * S * 2 * kv) * (2 + 4)  # val+idx
    total += 2 * B * H * nb * Dh * 2                     # fp16 streaming buffer
    total += B * 4                                       # per-slot lengths
    return total


def test_engine_cache_nbytes_matches_closed_form():
    eng, _ = _engines("gear")
    R = TINY.pattern_repeats
    ccfg = CacheConfig(batch=3, kv_heads=TINY.num_kv_heads, head_dim=TINY.head_dim,
                       capacity=48, policy=GEAR_POL)
    expected = R * _expected_gear_layer_bytes(ccfg)
    got = Engine.cache_nbytes(eng.init_caches())
    assert got == expected, (got, expected)

    fp16_eng, _ = _engines("fp16")
    fp16_cap = fp16_eng._cap()        # engine rounds 48 up to FP16's 64-buffer
    fp16_expected = R * (2 * 3 * TINY.num_kv_heads * fp16_cap * TINY.head_dim * 2
                         + 3 * 4)
    fp16_got = Engine.cache_nbytes(fp16_eng.init_caches())
    assert fp16_got == fp16_expected, (fp16_got, fp16_expected)


def test_gear_cache_strictly_below_fp16_at_paper_geometry():
    """The compression-ratio claim, pinned on real allocations: at the
    paper's serving geometry a GEAR layer cache is strictly smaller than the
    FP16 cache of the same capacity (the toy test geometry above is too
    small for chunk overheads to amortize — that regime is fp16's)."""
    from repro.core.cache import init_layer_cache

    pol = named_policy("gear_kcvt4")
    gear_cfg = CacheConfig(batch=2, kv_heads=8, head_dim=128, capacity=1024,
                           policy=pol)
    fp16_cfg = dataclasses.replace(gear_cfg, policy=FP16, kind="fp16")
    gear_bytes = Engine.cache_nbytes(init_layer_cache(gear_cfg))
    fp16_bytes = Engine.cache_nbytes(init_layer_cache(fp16_cfg))
    assert gear_bytes == _expected_gear_layer_bytes(gear_cfg)
    assert gear_bytes < fp16_bytes
    # 4-bit backbone + factors should land well under half of fp16
    assert gear_bytes / fp16_bytes < 0.55, gear_bytes / fp16_bytes


# ---------------------------------------------------------------------------
# Sharding: the slot-splice donation path keeps legal cache pspecs


def test_cache_pspecs_legal_and_splice_runs_under_mesh():
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_test_mesh

    # Use a real data-parallel axis when the topology allows (CI's full lane
    # fakes 8 host devices), so the traced-offset batch-row splice actually
    # crosses shard boundaries; single-device runs still smoke the specs.
    nd = jax.device_count()
    if nd >= 4:
        mesh = make_test_mesh(data=2, model=2)
    elif nd >= 2:
        mesh = make_test_mesh(data=2, model=1)
    else:
        mesh = make_test_mesh(data=1, model=1)
    cfg, pol = KINDS["gear"]
    model = build_model(cfg)
    cache_abs = jax.eval_shape(lambda: model.init_caches(pol, 2, 48))
    specs = shd.cache_pspecs(cfg, cache_abs, mesh, batch=2)
    # every spec must be realizable on the mesh (fit_spec already legalized)
    shd.shardings_for(mesh, specs)
    # per-slot scalars (length [R, B], window pos [R, B, W]) flow through too
    for leaf, spec in zip(jax.tree.leaves(cache_abs), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
        assert len(spec) <= len(leaf.shape)

    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(batch=2, capacity=48, policy=pol, eos_id=EOS),
                 mesh=mesh)
    caches = eng.init_caches()
    prompt = _requests(length=PROMPT_PAD)[0].tokens[None]
    _, caches = eng.prefill_slot({"tokens": jnp.asarray(prompt, jnp.int32)},
                                 caches, 1)
    tb = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    logits, _ = eng.decode(tb, caches, jnp.asarray([0, PROMPT_PAD], jnp.int32))
    assert logits.shape[0] == 2


# ---------------------------------------------------------------------------
# Streaming chunked prefill through the serving stack


def test_splice_isolation_streaming_prefill():
    """Continuous batching with ``prefill_mode="streaming"``: every spliced
    request's greedy tokens stay bit-identical to a solo run on a streaming
    engine — the compress-as-you-go pipeline preserves the batch-invariant
    compression and per-slot isolation the splice protocol relies on."""
    cfg, pol = KINDS["gear"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(batch=3, capacity=48, policy=pol, eos_id=EOS,
                        prefill_mode="streaming")
    eng = Engine(model, params, ecfg)
    solo = Engine(model, params, dataclasses.replace(ecfg, batch=1))
    sched = Scheduler(eng)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    out = {r.rid: r.tokens for r in sched.run_continuous()}
    for r in reqs:
        np.testing.assert_array_equal(
            out[r.rid], _solo_reference(solo, r),
            err_msg=f"streaming rid {r.rid} diverged from its solo run")


def test_streaming_and_monolithic_engine_caches_agree():
    """Engine-level prefill-mode parity.  Given identical K/V the two modes
    are bit-exact (pinned at cache level in test_cache); through the model
    the per-chunk vs full-sequence projection GEMMs may differ by 1 ulp of
    bf16, so here the caches must agree up to that jitter: identical
    geometry, (near-)identical leaves, a ≪1% budget of flipped codes."""
    cfg, pol = KINDS["gear"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = _requests(length=PROMPT_PAD)[0].tokens[None]
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)}
    caches = {}
    for mode in ("monolithic", "streaming"):
        _, caches[mode] = model.prefill(params, batch, pol, 48,
                                        prefill_mode=mode)
    assert (Engine.cache_nbytes(caches["monolithic"])
            == Engine.cache_nbytes(caches["streaming"]))
    # Leaf-wise bit comparison would be unstable (outlier *selection* is
    # discontinuous in the 1-ulp projection jitter), so compare what decode
    # actually consumes: the dense reconstruction of every layer cache.
    from repro.core.cache import dense_kv
    from repro.models.transformer import cache_cfg_for
    ccfg = cache_cfg_for(cfg, "global", pol, 1, 48)
    for r in range(cfg.pattern_repeats):
        lm = jax.tree.map(lambda t: t[r], caches["monolithic"][0])
        ls = jax.tree.map(lambda t: t[r], caches["streaming"][0])
        np.testing.assert_array_equal(np.asarray(lm.length), np.asarray(ls.length))
        for m_side, s_side in zip(dense_kv(ccfg, lm), dense_kv(ccfg, ls)):
            diff = np.abs(np.asarray(m_side) - np.asarray(s_side))
            assert float(diff.mean()) < 0.01         # jitter, not divergence
            assert float((diff > 0.05).mean()) < 0.01


@pytest.mark.parametrize("modality,arch", [("vlm", "paligemma-3b"),
                                           ("audio", "musicgen-medium")])
def test_engine_refuses_non_text_modality(modality, arch):
    """The engine serves token-id prompts only: a VLM or audio model is
    refused when the engine is built, naming its modality."""
    from repro.configs import smoke_config
    cfg = smoke_config(arch)
    assert cfg.modality == modality
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match=f"modality '{modality}'.*run_continuous"):
        Engine(model, params, EngineConfig(batch=1, capacity=48,
                                           policy=GEAR_POL))


def test_engine_config_rejects_unknown_prefill_mode():
    cfg, pol = KINDS["gear"]
    with pytest.raises(ValueError, match="prefill_mode"):
        EngineConfig(batch=1, capacity=48, policy=pol, prefill_mode="nope")


# ---------------------------------------------------------------------------
# Hypothesis property: splice-after-streaming-prefill is bit-exact

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hyp_st
    HAS_HYPOTHESIS = True
except ImportError:                                    # fast lane w/o extras
    HAS_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: f

    def settings(*a, **k):
        return lambda f: f

    class hyp_st:                                      # placeholder strategies
        integers = sampled_from = staticmethod(lambda *a, **k: None)


@pytest.mark.skipif(not HAS_HYPOTHESIS, reason="hypothesis not installed")
@given(seed=hyp_st.integers(0, 2**16),
       n_new=hyp_st.sampled_from([5, 8, 19]),
       slot=hyp_st.integers(0, 2))
@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow] if HAS_HYPOTHESIS else [])
def test_property_splice_after_streaming_prefill_bit_exact(seed, n_new, slot):
    """A batch-1 STREAMING prefill spliced into a live streaming-prefilled
    batch lands bit-exactly (spliced row == solo row, other rows untouched)
    for any prompt length phase (buffer-only / chunk-boundary / mixed) and
    any slot — the cache-level half of splice isolation for the new prefill
    pipeline."""
    from repro.core import (CacheConfig, init_layer_cache, named_policy,
                            splice_slot, streaming_prefill_layer_cache)
    B, H, DH = 3, 2, 32
    pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=8,
                              rank=2)
    ccfg = CacheConfig(batch=B, kv_heads=H, head_dim=DH, capacity=32, policy=pol)
    key = jax.random.PRNGKey(seed)
    qb = jax.random.normal(key, (B, 2 * H, 24, DH))
    kb = jax.random.normal(jax.random.fold_in(key, 1), (B, H, 24, DH))
    vb = jax.random.normal(jax.random.fold_in(key, 2), (B, H, 24, DH))
    live, _ = streaming_prefill_layer_cache(ccfg, init_layer_cache(ccfg),
                                            qb, kb, vb, DH**-0.5)

    cfg1 = dataclasses.replace(ccfg, batch=1)
    q1 = jax.random.normal(jax.random.fold_in(key, 3), (1, 2 * H, n_new, DH))
    k1 = jax.random.normal(jax.random.fold_in(key, 4), (1, H, n_new, DH))
    v1 = jax.random.normal(jax.random.fold_in(key, 5), (1, H, n_new, DH))
    solo, _ = streaming_prefill_layer_cache(cfg1, init_layer_cache(cfg1),
                                            q1, k1, v1, DH**-0.5)

    spliced = splice_slot(live, solo, slot)
    for name in ("k_packed", "v_packed", "k_scale", "v_scale", "k_a", "k_b",
                 "v_a", "v_b", "k_sp_val", "k_sp_idx", "v_sp_val", "v_sp_idx",
                 "buf_k", "buf_v", "length"):
        got, want, before = (getattr(spliced, name), getattr(solo, name),
                             getattr(live, name))
        if got is None:
            continue
        got, want, before = np.asarray(got), np.asarray(want), np.asarray(before)
        np.testing.assert_array_equal(got[slot], want[0], err_msg=name)
        others = [s for s in range(B) if s != slot]
        np.testing.assert_array_equal(got[others], before[others],
                                      err_msg=f"{name} (untouched rows)")


_BUCKET_ENGINE: list = []


def _bucket_engine() -> Engine:
    if not _BUCKET_ENGINE:
        cfg, pol = KINDS["gear"]
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _BUCKET_ENGINE.append(Engine(model, params, EngineConfig(
            batch=1, capacity=48, policy=pol, eos_id=-1,
            prefill_mode="streaming")))
    return _BUCKET_ENGINE[0]


@pytest.mark.skipif(not HAS_HYPOTHESIS, reason="hypothesis not installed")
@given(n=hyp_st.integers(2, 40), seed=hyp_st.integers(0, 2**16))
@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow] if HAS_HYPOTHESIS else [])
def test_property_length_bucketing_preserves_logits(n, seed):
    """Engine-side length bucketing (pad the prompt up to the next n_b
    multiple, run the padded-tail streaming pipeline) never changes WHAT
    the engine serves: cache lengths stay the raw length and the last-
    position logits match an exact-length streaming prefill.  The bucketed
    and exact tails attend at different static widths, so XLA may reorder
    the tail reductions — logits agree to round-off, not necessarily
    bit-for-bit.  (Warm vs cold BUCKETED runs, which share tail widths,
    ARE bitwise — see tests/test_prefixcache.py.)"""
    eng = _bucket_engine()
    assert eng._can_bucket
    rng = np.random.RandomState(seed)
    toks = rng.randint(4, 64, size=n)
    batch = {"tokens": jnp.asarray(toks[None], jnp.int32)}
    exact_logits, _ = eng._prefill(eng.params, batch)
    bucket_logits, bucket_caches = eng._cold_prefill(batch)
    for c in bucket_caches:
        np.testing.assert_array_equal(np.asarray(c.length), n)
    np.testing.assert_allclose(
        np.asarray(bucket_logits, np.float32),
        np.asarray(exact_logits, np.float32), atol=0.05, rtol=0.05)


def test_streaming_engine_falls_back_for_unsupported_layout():
    """An engine whose policy lacks the streaming layout (fine-grained K
    groups) still serves under prefill_mode="streaming": every layer takes
    the monolithic fallback, so prefill+decode run and match a monolithic
    engine bit-for-bit."""
    cfg, _ = KINDS["gear"]
    pol = dataclasses.replace(named_policy("gear_kivi2"), buffer_size=8,
                              group=4, rank=2, rank_decode=2)  # group != chunk
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    req = Request(rid=0, tokens=_requests(length=PROMPT_PAD)[0].tokens,
                  max_new_tokens=6)
    outs = {}
    for mode in ("monolithic", "streaming"):
        eng = Engine(model, params, EngineConfig(batch=1, capacity=48,
                                                 policy=pol, eos_id=-1,
                                                 prefill_mode=mode))
        sched = Scheduler(eng)
        sched.submit(req)
        (res,) = sched.run_continuous()
        assert len(res.tokens) == 6
        outs[mode] = res.tokens
    np.testing.assert_array_equal(outs["monolithic"], outs["streaming"])
