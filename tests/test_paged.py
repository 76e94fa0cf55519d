"""Paged compressed KV pool: paged ≡ dense bit-parity, allocator refcount
invariants, and pool-bytes-limited admission.

The archetype test is layout parity: a paged engine must produce caches,
logits, and greedy tokens bit-identical to the dense engine for the same
requests — across quant-only / low-rank / outlier GEAR policies and mixed
(windowed) layer trees.  This pins the zero-page invariant, the block-table
gather paths (kernel and oracle), the admission splice (zero + scatter +
row write), and refcounted prefix-page sharing all at once.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import cache as cache_lib
from repro.core.policy import FP16, named_policy
from repro.models.model import build_model
from repro.models.transformer import cache_cfg_for
from repro.serving import (AttendPath, CacheLayout, CacheView, DenseCacheView,
                           Engine, EngineConfig, PagedCacheView, PagePool,
                           PoolExhausted, PrefillMode, Request, Scheduler,
                           pages_needed)
from repro.serving.scheduler import _pad

EOS = 3
PROMPT_PAD = 8
CAP = 48

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                   num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                   vocab_size=64)
TINY_WIN = dataclasses.replace(TINY, attn_pattern="local_global",
                               pattern_locals=1, local_window=8)


def _small(name):
    pol = named_policy(name)
    return dataclasses.replace(pol, buffer_size=8, group=min(pol.group, 8),
                               rank=2, rank_decode=2)


_MODELS: dict = {}


def _model(cfg):
    key = cfg.name + cfg.attn_pattern
    if key not in _MODELS:
        m = build_model(cfg)
        _MODELS[key] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[key]


def _requests(n=5, seed=0):
    rng = np.random.RandomState(seed)
    budgets = [6, 3, 9, 1, 5, 7, 2][:n]
    return [Request(rid=i,
                    tokens=rng.randint(4, 64, size=rng.randint(2, PROMPT_PAD + 1)),
                    max_new_tokens=b)
            for i, b in enumerate(budgets)]


def _run(engine):
    sched = Scheduler(engine)
    for r in _requests():
        sched.submit(r)
    return {r.rid: r.tokens for r in sched.run_continuous()}, sched.last_stats


# ---------------------------------------------------------------------------
# Tentpole: paged ≡ dense (tokens, logits, caches)


@pytest.mark.parametrize("polname", ["gear_kcvt4", "kivi2", "gear_l_kivi2"])
def test_paged_matches_dense_tokens(polname):
    """Same requests through continuous batching: greedy tokens bit-equal
    across gear (lowrank+outlier), quant-only, and lowrank-only policies."""
    model, params = _model(TINY)
    pol = _small(polname)
    ecfg = EngineConfig(batch=3, capacity=CAP, policy=pol, eos_id=EOS)
    dense, _ = _run(Engine(model, params, ecfg))
    eng_p = Engine(model, params, dataclasses.replace(ecfg, layout="paged"))
    paged, stats = _run(eng_p)
    assert dense.keys() == paged.keys()
    for rid in dense:
        np.testing.assert_array_equal(dense[rid], paged[rid], err_msg=str(rid))
    eng_p.pool.check()
    assert stats["layout"] == "paged" and stats["pool"]["admits"] == 5


def test_paged_matches_dense_windowed_tree():
    """Mixed tree: window layers stay dense inside a paged engine and the
    whole model still matches the dense engine bit-for-bit."""
    model, params = _model(TINY_WIN)
    ecfg = EngineConfig(batch=2, capacity=CAP, policy=_small("gear_kcvt4"),
                        eos_id=EOS)
    dense, _ = _run(Engine(model, params, ecfg))
    eng_p = Engine(model, params, dataclasses.replace(ecfg, layout="paged"))
    paged, _ = _run(eng_p)
    for rid in dense:
        np.testing.assert_array_equal(dense[rid], paged[rid], err_msg=str(rid))


def test_paged_cache_and_logits_bitwise():
    """Slot prefill + decode steps: per-step logits and the slot's gathered
    cache row are bitwise equal to the dense layout's."""
    model, params = _model(TINY)
    pol = _small("gear_kcvt4")
    ecfg = EngineConfig(batch=3, capacity=CAP, policy=pol)
    eng_d = Engine(model, params, ecfg)
    eng_p = Engine(model, params, dataclasses.replace(ecfg, layout="paged"))
    cd, cp = eng_d.init_caches(), eng_p.init_caches()
    prompt = _pad(_requests()[0].tokens, PROMPT_PAD)[None]
    b1 = {"tokens": jnp.asarray(prompt, jnp.int32)}
    ld, cd = eng_d.prefill_slot(b1, cd, 1)
    lp, cp = eng_p.prefill_slot(b1, cp, 1, reserve_tokens=PROMPT_PAD + 20)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
    tok = jnp.asarray([[5], [7], [9]], jnp.int32)
    pos = jnp.asarray([0, PROMPT_PAD, 0], jnp.int32)
    for step in range(12):            # crosses a chunk boundary (n_b = 8)
        ld, cd = eng_d.decode({"tokens": tok}, cd, pos + step)
        lp, cp = eng_p.decode({"tokens": tok}, cp, pos + step)
        np.testing.assert_array_equal(np.asarray(ld[1]), np.asarray(lp[1]),
                                      err_msg=f"step {step}")
    ccfg = cache_cfg_for(TINY, "global", pol, 3, CAP)
    bt = jnp.asarray(eng_p.pool.block_tables)
    for i in range(len(cd)):
        for r in range(TINY.pattern_repeats):
            dl = jax.tree.map(lambda t: t[r], cd[i])
            dn = cache_lib.paged_to_dense(
                ccfg, jax.tree.map(lambda t: t[r], cp[i]), bt)
            for f in cache_lib._POOLED_FIELDS + ("buf_k", "buf_v", "length"):
                a = getattr(dl, f)
                if a is None:
                    assert getattr(dn, f) is None
                    continue
                # only the live slot's row is comparable: idle DENSE rows
                # accumulate garbage appends the paged layout drops by design
                np.testing.assert_array_equal(
                    np.asarray(a)[1], np.asarray(getattr(dn, f))[1],
                    err_msg=f"pos{i} r{r} {f}")


def test_paged_prefix_cache_shares_pages():
    """Shared-system-prompt workload: warm paged engine matches the cold
    dense engine bit-for-bit AND serves hits by page refcount (COW never
    copies — shared_pages > 0, zero payload bytes duplicated)."""
    model, params = _model(TINY)
    pol = _small("gear_kcvt4")
    base = EngineConfig(batch=2, capacity=64, policy=pol, eos_id=EOS,
                        prefill_mode="streaming")
    rng = np.random.RandomState(1)
    sys_prompt = rng.randint(4, 64, size=24)
    sfx = [rng.randint(4, 64, size=6) for _ in range(4)]
    reqs = lambda: [Request(rid=i, tokens=np.concatenate([sys_prompt, sfx[i]]),
                            max_new_tokens=5) for i in range(4)]

    def run(eng):
        s = Scheduler(eng)
        for r in reqs():
            s.submit(r)
        return {r.rid: r.tokens for r in s.run_continuous()}, s.last_stats

    cold, _ = run(Engine(model, params, base))
    eng_w = Engine(model, params, dataclasses.replace(
        base, layout="paged", prefix_cache=True))
    warm, st = run(eng_w)
    for rid in cold:
        np.testing.assert_array_equal(cold[rid], warm[rid], err_msg=str(rid))
    eng_w.pool.check()
    assert st["prefix_hit_rate"] > 0
    assert st["pool"]["shared_pages"] > 0


# ---------------------------------------------------------------------------
# Admission: pool-bytes-limited, OOM queues instead of crashing


def test_oom_admission_queues_not_crashes():
    """Pool sized for ONE in-flight request on a 2-slot engine: every
    request still completes (serially), bit-identical to a roomy pool."""
    model, params = _model(TINY)
    pol = _small("gear_kcvt4")
    ecfg = EngineConfig(batch=2, capacity=CAP, policy=pol, eos_id=EOS,
                        layout="paged")
    roomy, _ = _run(Engine(model, params, ecfg))
    # need = raw prompt (<= 8) + max_new - 1 <= 16 -> 2 pages of n_b=8
    tight = Engine(model, params, dataclasses.replace(ecfg, pool_pages=3))
    got, stats = _run(tight)
    for rid in roomy:
        np.testing.assert_array_equal(roomy[rid], got[rid], err_msg=str(rid))
    tight.pool.check()
    assert stats["pool"]["admits"] == 5        # every request got a slot
    # finished slots keep their reservation (like dense rows keep data)
    # until re-spliced or reset; dropping them returns every page
    for s in range(2):
        tight.pool.release_slot(s)
    assert tight.pool.free_pages == 2


def test_submit_rejects_impossible_request():
    model, params = _model(TINY)
    eng = Engine(model, params, EngineConfig(
        batch=2, capacity=CAP, policy=_small("gear_kcvt4"),
        layout="paged", pool_pages=2))        # 1 allocatable page
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="pool pages"):
        sched.submit(Request(rid=0, tokens=np.arange(4), max_new_tokens=30))


def test_pool_exhausted_is_retryable():
    pool = PagePool(n_pages=4, batch=2, n_chunks=6, page_bytes=128)
    pool.admit(0, 2)
    with pytest.raises(PoolExhausted):
        pool.admit(1, 2)
    pool.check()                               # state unchanged by the raise
    assert pool.free_pages == 1
    pool.release_slot(0)
    assert len(pool.admit(1, 2)) == 2          # retry succeeds


# ---------------------------------------------------------------------------
# Typed config shim


def test_engine_config_enum_coercion():
    pol = _small("gear_kcvt4")
    ecfg = EngineConfig(batch=1, capacity=CAP, policy=pol, fused="interpret",
                        prefill_mode="streaming", layout="paged")
    assert ecfg.fused is AttendPath.INTERPRET
    assert ecfg.prefill_mode is PrefillMode.STREAMING
    assert ecfg.layout is CacheLayout.PAGED
    # str-mixin: legacy string comparisons keep working
    assert ecfg.fused == "interpret" and str(ecfg.layout) == "paged"
    # enum members pass through unchanged
    assert EngineConfig(batch=1, capacity=CAP, policy=pol,
                        fused=AttendPath.OFF).fused is AttendPath.OFF


def test_engine_config_rejects_bad_knobs():
    pol = _small("gear_kcvt4")
    with pytest.raises(ValueError, match="fused"):
        EngineConfig(batch=1, capacity=CAP, policy=pol, fused="sometimes")
    with pytest.raises(ValueError, match="layout"):
        EngineConfig(batch=1, capacity=CAP, policy=pol, layout="ragged")
    with pytest.raises(ValueError, match="not both"):
        EngineConfig(batch=1, capacity=CAP, policy=pol, layout="paged",
                     pool_pages=4, pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="pool_pages"):
        EngineConfig(batch=1, capacity=CAP, policy=pol, pool_pages=4)
    with pytest.raises(ValueError, match="fp16"):
        model, params = _model(TINY)
        Engine(model, params, EngineConfig(batch=1, capacity=CAP, policy=FP16,
                                           layout="paged"))


def test_cache_view_facade():
    """new_view returns the layout's CacheView; both satisfy the protocol
    and the dense view reproduces the raw-tree API bit-for-bit."""
    model, params = _model(TINY)
    pol = _small("gear_kcvt4")
    ecfg = EngineConfig(batch=2, capacity=CAP, policy=pol)
    eng_d = Engine(model, params, ecfg)
    eng_p = Engine(model, params, dataclasses.replace(ecfg, layout="paged"))
    vd, vp = eng_d.new_view(), eng_p.new_view()
    assert isinstance(vd, DenseCacheView) and isinstance(vd, CacheView)
    assert isinstance(vp, PagedCacheView) and isinstance(vp, CacheView)
    assert vd.can_admit(10**9)                 # slot-count-limited
    assert vp.can_admit(CAP) and not vp.can_admit(10**9)

    prompt = _pad(_requests()[0].tokens, PROMPT_PAD)[None]
    b1 = {"tokens": jnp.asarray(prompt, jnp.int32)}
    lv = vd.prefill_slot(b1, 0)
    caches = eng_d.init_caches()
    lr, caches = eng_d.prefill_slot(b1, caches, 0)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lr))
    tok = {"tokens": jnp.asarray([[5], [7]], jnp.int32)}
    pos = jnp.asarray([PROMPT_PAD, 0], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(vd.decode(tok, pos)),
        np.asarray(eng_d.decode(tok, caches, pos)[0]))
    vp.prefill_slot(b1, 1, reserve_tokens=16)
    vp.decode(tok, pos[::-1])
    vp.reset_slot(1)
    eng_p.pool.check()


# ---------------------------------------------------------------------------
# Hypothesis property: allocator refcount conservation

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st
    HAS_HYPOTHESIS = True
except ImportError:                                    # fast lane w/o extras
    HAS_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: f

    def settings(*a, **k):
        return lambda f: f

    class hyp_st:                                      # placeholder strategies
        integers = lists = tuples = staticmethod(lambda *a, **k: None)


def _pool_interleaving(ops):
    """Drive random admit / release / retain(COW) / store-free interleavings
    and audit the allocator's invariants after every op: no page both free
    and live, no double frees, page 0 never allocated, byte accounting
    exact, and every reference eventually returned."""
    pool = PagePool(n_pages=9, batch=3, n_chunks=6, page_bytes=64)
    handles: list[int] = []
    for kind, slot, n in ops:
        if kind == 0:                               # admit (maybe sharing)
            if pool.slot_pages(slot).size:
                pool.release_slot(slot)
            live = [h for h in handles if pool.refcount(h) > 0]
            shared = live[: n // 2]
            try:
                pool.admit(slot, min(n + len(shared), pool.n_chunks),
                           shared=shared)
            except PoolExhausted:
                pass
        elif kind == 1:                             # release a slot
            pool.release_slot(slot)
        elif kind == 2:                             # trie retain
            pages = pool.slot_pages(slot)
            if pages.size:
                handles.append(pool.retain(int(pages[n % pages.size])))
        elif kind == 3 and handles:                 # trie eviction
            pool.release(handles.pop(n % len(handles)))
        pool.check()
        assert pool.used_bytes == pool.used_pages * pool.page_bytes
        assert pool.used_pages + pool.free_pages == pool.n_pages - 1
    for slot in range(3):
        pool.release_slot(slot)
    for h in handles:
        pool.release(h)
    pool.check()
    assert pool.free_pages == pool.n_pages - 1      # everything came back


@pytest.mark.skipif(not HAS_HYPOTHESIS, reason="hypothesis not installed")
@given(ops=hyp_st.lists(
    hyp_st.tuples(hyp_st.integers(0, 3),      # op kind
                  hyp_st.integers(0, 2),      # slot
                  hyp_st.integers(1, 5)),     # page count / page pick
    min_size=1, max_size=60))
@settings(max_examples=50, deadline=None)
def test_pool_refcounts_under_random_interleaving(ops):
    _pool_interleaving(ops)


def test_pool_refcounts_seeded_interleavings():
    """Deterministic stand-in for the hypothesis property (runs with or
    without the extra): 32 seeded random op sequences."""
    for seed in range(32):
        rng = np.random.RandomState(seed)
        ops = [(int(rng.randint(0, 4)), int(rng.randint(0, 3)),
                int(rng.randint(1, 6)))
               for _ in range(int(rng.randint(1, 61)))]
        _pool_interleaving(ops)


def test_pages_needed():
    assert pages_needed(0, 8) == 0
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2


# ---------------------------------------------------------------------------
# Page layout: lane-dense pages, converted only where chunks enter or leave


def _chunk_fields(cfg, n, seed=0):
    """Random dense chunk fields [n, H, *chunk shape] of every pooled field:
    codes over all 32 bits, indices anywhere in their range."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, dtype) in cache_lib.chunk_field_shapes(cfg).items():
        full = (n,) + shape
        if name.endswith("_packed"):
            x = rng.randint(-2**31, 2**31 - 1, size=full, dtype=np.int64)
        elif name.endswith("_idx"):
            x = rng.randint(0, max(cfg.chunk, cfg.head_dim), size=full)
        else:
            x = rng.standard_normal(full)
        out[name] = jnp.asarray(x).astype(dtype)
    return out


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("polname", ["gear_kcvt4", "gear_kivi2", "gear_l_kcvt4",
                                     "outlier_kivi2", "kcvt4"])
def test_chunk_page_roundtrip_bit_exact(polname, head_dim):
    """Dense chunk -> page -> dense is bit-exact for every pooled field, and
    a page is lane-dense: both leaves [rows, 128] in whole tiles."""
    cfg = cache_lib.CacheConfig(batch=1, kv_heads=2, head_dim=head_dim,
                                capacity=256, policy=named_policy(polname))
    layout = cache_lib.page_layout(cfg)
    fields = _chunk_fields(cfg, 3)
    pages = cache_lib.chunks_to_pages(layout, fields)
    for leaf, ((rows, lanes), dtype) in layout.leaf_shapes().items():
        assert pages[leaf].shape == (3, rows, lanes) and lanes == 128
        assert pages[leaf].dtype == dtype and rows % (32 // dtype.itemsize) == 0
    back = cache_lib.pages_to_chunks(layout, pages)
    assert back.keys() == fields.keys()
    for name, x in fields.items():
        assert back[name].dtype == x.dtype and back[name].shape == x.shape
        np.testing.assert_array_equal(
            np.asarray(back[name]).view(np.uint8),
            np.asarray(x).view(np.uint8), err_msg=name)


def test_page_bytes_at_the_served_geometry():
    """starcoder2-3b's GEAR layer (2 KV heads of 128, chunks of 64,
    GEAR-KCVT-4bit): a layer-page costs no more than the per-field layout's
    30,208 B, and the benchmark cell's ``pool_bytes`` over 30 layers still
    gives the 513 pages its 16 slots x 2048 tokens need."""
    from repro.configs import get_config
    cfg = get_config("starcoder2-3b")
    ccfg = cache_cfg_for(cfg, "global", named_policy("gear_kcvt4"), 1, 2048)
    nbytes = cache_lib.page_nbytes(ccfg)
    assert nbytes <= 30_208
    assert cache_lib.page_field_shapes(ccfg) == {
        "codes": ((32, 128), jnp.dtype(jnp.int32)),
        "side": ((48, 128), jnp.dtype(jnp.bfloat16))}
    n_pages = 463_994_880 // (cfg.num_layers * nbytes) + 1   # Engine's sizing
    assert n_pages >= 16 * 2048 // 64 + 1 == 513
