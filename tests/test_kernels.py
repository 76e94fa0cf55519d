"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig, named_policy, init_layer_cache, prefill_layer_cache
from repro.core.cache import chunks_to_pages, page_layout
from repro.kernels.quant_pack import quant_pack
from repro.kernels.gear_decode import gear_decode, gear_decode_paged
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels import ref

pytestmark = pytest.mark.kernel


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("N,n,d", [(4, 64, 128), (2, 16, 64), (1, 64, 256), (8, 32, 32)])
def test_quant_pack_sweep(bits, N, n, d, rng):
    from repro.core import packing
    x = jax.random.normal(rng, (N, n, d), jnp.float32)
    pk, sk, zk = quant_pack(x, bits, interpret=True)
    pr, sr, zr = ref.quant_pack_ref(x, bits)
    assert jnp.allclose(sk, sr) and jnp.allclose(zk, zr)
    # The kernel and the oracle are separately-compiled XLA programs; fma/
    # fusion ordering can flip values sitting exactly on a round-half
    # boundary by ±1 code (≪0.1% of entries).  Allow exactly that jitter.
    ck = packing.unpack(pk, bits, d)
    cr = packing.unpack(pr, bits, d)
    diff = jnp.abs(ck - cr)
    assert int(diff.max()) <= 1
    assert float((diff > 0).mean()) < 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_pack_dtypes(dtype, rng):
    from repro.core import packing
    x = jax.random.normal(rng, (2, 64, 128)).astype(dtype)
    pk, sk, zk = quant_pack(x, 4, interpret=True)
    pr, sr, zr = ref.quant_pack_ref(x, 4)
    assert jnp.allclose(sk, sr) and jnp.allclose(zk, zr)
    if dtype == jnp.float32:
        assert (pk == pr).all()
    else:
        # bf16 inputs hit round-half boundaries where fma ordering flips the
        # code by ±1 (≪0.1% of entries) — allow exactly that jitter.
        ck = packing.unpack(pk, 4, 128)
        cr = packing.unpack(pr, 4, 128)
        diff = jnp.abs(ck - cr)
        assert int(diff.max()) <= 1
        assert float((diff > 0).mean()) < 1e-3


def _cache_arrays(polname, B=2, H=2, Dh=128, S=128, n=100, nb=None):
    pol = named_policy(polname)
    if nb:
        pol = dataclasses.replace(pol, buffer_size=nb, group=min(pol.group, nb))
    cfg = CacheConfig(batch=B, kv_heads=H, head_dim=Dh, capacity=S, policy=pol)
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (B, H, n, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, H, n, Dh))
    cache = prefill_layer_cache(cfg, init_layer_cache(cfg), k, v)
    BH = B * H
    flat = lambda x: None if x is None else x.reshape((BH,) + x.shape[2:])
    n_comp = (cache.length[0] // cfg.chunk) * cfg.chunk  # uniform slots
    common = (flat(cache.k_packed), flat(cache.k_scale), flat(cache.k_zero),
              flat(cache.v_packed), flat(cache.v_scale), flat(cache.v_zero), n_comp)
    extras = dict(
        k_a=flat(cache.k_a), k_b=flat(cache.k_b), v_a=flat(cache.v_a),
        v_b=flat(cache.v_b), k_sp_val=flat(cache.k_sp_val),
        k_sp_idx=flat(cache.k_sp_idx), v_sp_val=flat(cache.v_sp_val),
        v_sp_idx=flat(cache.v_sp_idx))
    extras = {k2: v2 for k2, v2 in extras.items() if v2 is not None}
    return cfg, common, extras


def _assert_empty_triple(acc, m, l):
    """A row at extent 0 walks no chunk: (acc, m, l) is the init triple."""
    assert (acc == 0).all() and (m == ref.NEG_INF).all() and (l == 0).all()


@pytest.mark.parametrize("polname", ["gear_kivi2", "gear_l_kivi2", "kivi2",
                                     "gear_kcvt4", "kcvt4", "outlier_kivi2"])
@pytest.mark.parametrize("G,Dh,S", [(2, 128, 128), (1, 64, 64), (4, 128, 192)])
def test_gear_decode_sweep(polname, G, Dh, S, rng):
    nb = 64 if S % 64 == 0 else 32
    cfg, common, extras = _cache_arrays(polname, Dh=Dh, S=S, n=S - 10, nb=nb)
    q = jax.random.normal(rng, (4, G, Dh))
    kwargs = dict(bits=cfg.policy.bits, chunk=cfg.chunk, scale_factor=Dh**-0.5)
    acc_r, m_r, l_r = ref.gear_decode_ref(q, *common, **kwargs, **extras)
    acc_k, m_k, l_k = gear_decode(q, *common, interpret=True, **kwargs, **extras)
    assert jnp.allclose(m_k[..., 0], m_r, atol=1e-4)
    if common[-1] == 0:          # S - 10 tokens close no chunk of 64: empty rows
        _assert_empty_triple(acc_k, m_k[..., 0], l_k[..., 0])
        _assert_empty_triple(acc_r, m_r, l_r)
        return
    out_r = acc_r / l_r[..., None]
    out_k = acc_k / l_k[..., 0:1]
    assert jnp.allclose(out_k, out_r, atol=1e-4), float(jnp.abs(out_k - out_r).max())


@pytest.mark.parametrize("polname", ["gear_kivi2", "gear_kcvt4", "kivi2"])
def test_gear_decode_ragged_sweep(polname, rng):
    """Per-row compressed extents: the ragged kernel matches the ragged
    oracle, and every row matches a solo (batch-of-one) oracle call at that
    row's scalar extent — extents cover empty (0), one chunk, a mid-cache
    chunk boundary, and the full cache."""
    nb = 32
    cfg, common, extras = _cache_arrays(polname, B=2, H=2, Dh=64, S=128,
                                        n=128, nb=nb)
    arrays = common[:-1]
    q = jax.random.normal(rng, (4, 2, 64))
    kwargs = dict(bits=cfg.policy.bits, chunk=nb, scale_factor=64**-0.5)
    n_comp = jnp.asarray([0, nb, 3 * nb, 4 * nb], jnp.int32)   # one per bh row

    acc_r, m_r, l_r = ref.gear_decode_ref(q, *arrays, n_comp, **kwargs, **extras)
    acc_k, m_k, l_k = gear_decode(q, *arrays, n_comp, interpret=True,
                                  **kwargs, **extras)
    assert jnp.allclose(m_k[..., 0], m_r, atol=1e-4)
    # the empty row keeps the init triple (its acc / l would be 0 / 0)
    _assert_empty_triple(acc_k[:1], m_k[:1, :, 0], l_k[:1, :, 0])
    _assert_empty_triple(acc_r[:1], m_r[:1], l_r[:1])
    assert jnp.allclose(acc_k[1:] / l_k[1:, :, 0:1],
                        acc_r[1:] / l_r[1:, :, None], atol=1e-4)

    # row independence: each ragged row == a solo call at its scalar extent
    for x in range(1, 4):                                      # skip the empty row
        sl = lambda a: None if a is None else a[x:x + 1]
        acc_s, m_s, l_s = ref.gear_decode_ref(
            q[x:x + 1], *[sl(a) for a in arrays], n_comp[x], **kwargs,
            **{k: sl(v) for k, v in extras.items()})
        assert jnp.allclose(acc_r[x:x + 1], acc_s, rtol=1e-6, atol=1e-6)
        assert jnp.allclose(m_r[x:x + 1], m_s) and jnp.allclose(l_r[x:x + 1], l_s)


def test_gear_decode_scalar_extent_still_accepted(rng):
    """Back-compat: a scalar n_comp broadcasts to every row."""
    cfg, common, extras = _cache_arrays("gear_kcvt4", Dh=64, S=64, n=64, nb=32)
    arrays, scalar = common[:-1], common[-1]
    q = jax.random.normal(rng, (4, 2, 64))
    kwargs = dict(bits=cfg.policy.bits, chunk=32, scale_factor=64**-0.5)
    vec = jnp.full((4,), scalar, jnp.int32)
    for fn in (ref.gear_decode_ref,
               lambda *a, **k: gear_decode(*a, interpret=True, **k)):
        acc_s, m_s, l_s = fn(q, *arrays, scalar, **kwargs, **extras)
        acc_v, m_v, l_v = fn(q, *arrays, vec, **kwargs, **extras)
        assert (acc_s == acc_v).all() and (m_s == m_v).all() and (l_s == l_v).all()


HEAD = ("k_packed", "k_scale", "k_zero", "v_packed", "v_scale", "v_zero")
# an empty row, a partial chunk (live chunks = ceil(40 / 32) = 2), a chunk
# boundary and the full capacity of 8 chunks of 32
DEAD_EXTENTS = (0, 40, 96, 256)


def _junk(x):
    """NaN for float operands, the largest code for integer ones."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.asarray(jnp.nan, x.dtype)
    return jnp.asarray(jnp.iinfo(x.dtype).max, x.dtype)


def _poison_past(x, live, n_chunks):
    """Rows [N, X, ...] (X = n_chunks chunks of rows): every chunk at or past
    row i's first ``live[i]`` chunks becomes junk."""
    chunk = jnp.arange(x.shape[1]) // (x.shape[1] // n_chunks)
    dead = chunk[None, :] >= jnp.asarray(live)[:, None]
    return jnp.where(dead.reshape(dead.shape + (1,) * (x.ndim - 2)), _junk(x), x)


def _decode_case(polname, B, nb=32):
    """Dense kernel operands of ``B`` slots x 2 heads over a full 256-token
    cache, as one dict, with the call kwargs."""
    cfg, common, extras = _cache_arrays(polname, B=B, H=2, Dh=64, S=256,
                                        n=256, nb=nb)
    ops = dict(zip(HEAD, common[:-1])) | extras
    kwargs = dict(bits=cfg.policy.bits, chunk=nb, scale_factor=64**-0.5)
    return cfg, ops, kwargs


def _call(fn, q, ops, n_comp, *tables, **kwargs):
    return fn(q, *(ops[k] for k in HEAD), n_comp, *tables,
              **{k: v for k, v in ops.items() if k not in HEAD}, **kwargs)


def _assert_matches_oracle(got, want, live_rows):
    """Kernel triple ``got`` (m, l carried on lanes) against an oracle
    triple on clean data: normalized outputs on live rows, init triple on
    empty rows."""
    (acc_k, m_k, l_k), (acc_r, m_r, l_r) = got, want
    m_k, l_k = m_k[..., 0], l_k[..., 0]
    live = jnp.asarray(live_rows)
    assert jnp.isfinite(acc_k).all() and jnp.isfinite(l_k).all()
    assert jnp.allclose(m_k[live], m_r[live], atol=1e-4)
    assert jnp.allclose(acc_k[live] / l_k[live][..., None],
                        acc_r[live] / l_r[live][..., None], atol=1e-4)
    empty = jnp.asarray([x for x in range(m_k.shape[0]) if x not in live_rows])
    _assert_empty_triple(acc_k[empty], m_k[empty], l_k[empty])
    _assert_empty_triple(acc_r[empty], m_r[empty], l_r[empty])


@pytest.mark.parametrize("polname", ["gear_kcvt4", "gear_kivi2", "kivi2"])
def test_gear_decode_never_reads_dead_chunks(polname, rng):
    """Every chunk past a row's live extent holds NaN / junk: the kernel
    stays finite, equals its own run on clean data bit for bit, and matches
    the oracle run on clean data.  Extent 40 pins the walk's end at
    ceil(n_comp / chunk): its second chunk is live, and masked past token
    40."""
    nb, C = 32, 8
    cfg, ops, kwargs = _decode_case(polname, B=2, nb=nb)
    n_comp = jnp.asarray(DEAD_EXTENTS, jnp.int32)           # one per bh row
    live = -(-n_comp // nb)
    bad = {k: _poison_past(v, live, C) for k, v in ops.items()}
    q = jax.random.normal(rng, (4, 3, 64))

    kern = lambda o: _call(gear_decode, q, o, n_comp, interpret=True, **kwargs)
    got = kern(bad)
    for a, b in zip(got, kern(ops)):
        assert (a == b).all()
    want = _call(ref.gear_decode_ref, q, ops, n_comp, **kwargs)
    _assert_matches_oracle(got, want, live_rows=[1, 2, 3])


def _paged_pool(cfg, ops: dict, B: int, H: int, live, n_chunks: int):
    """Dense kernel rows [B*H, X, ...] -> the pool's lane-dense page leaves
    ``{"codes", "side": [P, rows, 128]}`` (``cache.chunks_to_pages``) and
    block tables [B, n_chunks].  Each live chunk gets a page of its own;
    every dead table entry points at page 0, which holds NaN / junk."""
    layout = page_layout(cfg)
    bt = np.zeros((B, n_chunks), np.int32)
    owners = [(b, c) for b in range(B) for c in range(int(live[b]))]
    for page, (b, c) in enumerate(owners, start=1):
        bt[b, c] = page
    slot_of = np.asarray([b for b, _ in owners], np.int32)
    chunk_of = np.asarray([c for _, c in owners], np.int32)
    fields = {}
    for name, x in ops.items():
        rows = x.shape[1] // n_chunks
        xs = x.reshape((B, H, n_chunks, rows) + x.shape[2:])
        fields[name] = xs[slot_of, :, chunk_of]          # [pages, H, rows, ...]
    pools = {leaf: jnp.concatenate([jnp.full((1,) + x.shape[1:], _junk(x)), x])
             for leaf, x in chunks_to_pages(layout, fields).items()}
    return layout, pools, jnp.asarray(bt)


def _paged_call(fn, q, layout, pools, n_slot, bt, **kwargs):
    return fn(q, pools["codes"], pools["side"], n_slot, bt, layout=layout,
              scale_factor=kwargs["scale_factor"],
              **({"interpret": True} if fn is gear_decode_paged else {}))


@pytest.mark.parametrize("polname", ["gear_kcvt4", "gear_kivi2", "kivi2"])
def test_gear_decode_paged_never_reads_dead_pages(polname, rng):
    """Dead table entries point at a NaN / junk page (not the zero page):
    the paged kernel stays finite, equals the dense kernel on clean data bit
    for bit, and matches the oracle run on clean data."""
    nb, C, B, H = 32, 8, 4, 2
    cfg, ops, kwargs = _decode_case(polname, B=B, nb=nb)
    n_slot = jnp.asarray(DEAD_EXTENTS, jnp.int32)           # one per slot
    n_comp = jnp.repeat(n_slot, H)
    layout, pools, bt = _paged_pool(cfg, ops, B, H, -(-n_slot // nb), C)
    q = jax.random.normal(rng, (B * H, 3, 64))

    got = _paged_call(gear_decode_paged, q, layout, pools, n_slot, bt,
                      **kwargs)
    dense = _call(gear_decode, q, ops, n_comp, interpret=True, **kwargs)
    for a, b in zip(got, dense):
        assert (a == b).all()
    want = _call(ref.gear_decode_ref, q, ops, n_comp, **kwargs)
    _assert_matches_oracle(got, want, live_rows=list(range(H, B * H)))


@pytest.mark.parametrize("polname", ["gear_kcvt4", "gear_kivi2", "kivi2"])
def test_gear_decode_empty_extent_triple(polname, rng):
    """Extent 0 everywhere: both kernels and every oracle return the init
    triple (0, NEG_INF, 0) on every row."""
    nb, C, B, H = 32, 8, 2, 2
    cfg, ops, kwargs = _decode_case(polname, B=B, nb=nb)
    n_comp = jnp.zeros((B * H,), jnp.int32)
    n_slot = jnp.zeros((B,), jnp.int32)
    layout, pools, bt = _paged_pool(cfg, ops, B, H, [0] * B, C)
    q = jax.random.normal(rng, (B * H, 3, 64))
    for acc, m, l in (
            _call(gear_decode, q, ops, n_comp, interpret=True, **kwargs),
            _paged_call(gear_decode_paged, q, layout, pools, n_slot, bt,
                        **kwargs)):
        _assert_empty_triple(acc, m, l)
    zero_page = {k: jnp.zeros_like(v) for k, v in pools.items()}
    for triple in (_call(ref.gear_decode_ref, q, ops, n_comp, **kwargs),
                   _call(ref.gear_hist_block_ref, q, ops, n_comp, **kwargs),
                   _paged_call(ref.gear_decode_paged_ref, q, layout,
                               zero_page, n_slot, bt, **kwargs)):
        _assert_empty_triple(*triple)


@pytest.mark.parametrize("polname", ["gear_kcvt4", "gear_kivi2", "kivi2"])
@pytest.mark.parametrize("n_comp", [0, 32, 64])
def test_gear_attend_block_view_wider_than_extent(polname, n_comp, rng):
    """Streaming prefill attends each chunk's queries against a prefix view
    wider than its extent (the attend scan's segments): with every view
    chunk past ``n_comp`` NaN / junk, the kernel path stays finite and
    matches the oracle path on clean data."""
    from repro.kernels import ops as kernel_ops
    nb, C, B, H, Dh = 32, 4, 2, 2, 64
    pol = named_policy(polname)
    pol = dataclasses.replace(pol, buffer_size=nb, group=min(pol.group, nb))
    cfg = CacheConfig(batch=B, kv_heads=H, head_dim=Dh, capacity=C * nb,
                      policy=pol)
    keys = jax.random.split(rng, 5)
    k = jax.random.normal(keys[0], (B, H, C * nb, Dh))
    v = jax.random.normal(keys[1], (B, H, C * nb, Dh))
    cache = prefill_layer_cache(cfg, init_layer_cache(cfg), k, v)
    live = jnp.full((B * H,), -(-n_comp // nb))
    bad = dataclasses.replace(cache, **{
        f: _poison_past(x.reshape((B * H,) + x.shape[2:]), live, C
                        ).reshape(x.shape)
        for f in HEAD + ("k_a", "k_b", "v_a", "v_b", "k_sp_val", "k_sp_idx",
                         "v_sp_val", "v_sp_idx")
        if (x := getattr(cache, f)) is not None})
    q = jax.random.normal(keys[2], (B, 2 * H, nb, Dh))
    k_blk = jax.random.normal(keys[3], (B, H, nb, Dh))
    v_blk = jax.random.normal(keys[4], (B, H, nb, Dh))
    o_ref = kernel_ops.gear_attend_block(cfg, cache, q, k_blk, v_blk,
                                         n_comp, nb, Dh**-0.5)
    o_krn = kernel_ops.gear_attend_block(cfg, bad, q, k_blk, v_blk,
                                         n_comp, nb, Dh**-0.5,
                                         force_kernel=True, interpret=True)
    assert jnp.isfinite(o_krn).all()
    assert jnp.allclose(o_krn, o_ref, atol=1e-4), float(jnp.abs(o_krn - o_ref).max())


@pytest.mark.parametrize("S,Dh,bq,bk", [(128, 64, 32, 32), (256, 128, 64, 64),
                                        (64, 64, 64, 16), (128, 256, 32, 128)])
@pytest.mark.parametrize("window,prefix,cap", [(0, 0, 0.0), (48, 0, 0.0),
                                               (0, 24, 0.0), (0, 0, 20.0)])
def test_flash_prefill_sweep(S, Dh, bq, bk, window, prefix, cap, rng):
    q = jax.random.normal(rng, (2, S, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, S, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, S, Dh), jnp.float32)
    o_k = flash_prefill(q, k, v, bq=bq, bk=bk, window=window, prefix_len=prefix,
                        softcap=cap, interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, jnp.arange(S), causal=True, window=window,
                                prefix_len=prefix, softcap=cap)
    assert jnp.allclose(o_k, o_r, atol=2e-4), float(jnp.abs(o_k - o_r).max())


def test_flash_prefill_bf16(rng):
    q = jax.random.normal(rng, (2, 128, 64)).astype(jnp.bfloat16)
    k, v = q + 0.1, q - 0.1
    o_k = flash_prefill(q, k, v, bq=32, bk=32, interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, jnp.arange(128))
    assert jnp.allclose(o_k.astype(jnp.float32), o_r.astype(jnp.float32), atol=3e-2)


@pytest.mark.parametrize("mode", ["inclusive", "bonus"])
@pytest.mark.parametrize("S,Dk,Dv,chunk", [(64, 8, 16, 16), (128, 16, 16, 64),
                                           (32, 4, 8, 8)])
def test_linear_scan_kernel_sweep(mode, S, Dk, Dv, chunk, rng):
    from repro.kernels.linear_scan_kernel import linear_scan_chunked
    from repro.models.linear_scan import chunked_scan
    B, H = 2, 2
    r = jax.random.normal(rng, (B, H, S, Dk))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, H, S, Dk))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, H, S, Dv))
    lw = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 3), (B, H, S, Dk)))
    u = jax.random.normal(jax.random.fold_in(rng, 4), (H, Dk)) * 0.5
    y_ref, st_ref = chunked_scan(r, k, v, lw, chunk=chunk, u=u, mode=mode)
    BH = B * H
    fl = lambda x: x.reshape((BH,) + x.shape[2:])
    uu = jnp.broadcast_to(u[None], (B, H, Dk)).reshape(BH, Dk)
    y_k, st_k = linear_scan_chunked(fl(r), fl(k), fl(v), fl(lw), u=uu,
                                    chunk=chunk, mode=mode, interpret=True)
    assert jnp.allclose(y_k.reshape(B, H, S, Dv), y_ref, atol=2e-3)
    assert jnp.allclose(st_k.reshape(B, H, Dk, Dv), st_ref, atol=2e-3)


# ---------------------------------------------------------------------------
# Fused chunk compression (gear_compress)


def _lattice_chunks(key, N, nb, d, bits=4, delta=0.5):
    """Two-level {0, top} chunk batch: every quantization group, under ANY
    grouping, sees scale = delta exactly (or the eps floor for constant
    groups), and outlier removal keeps the remainder on the lattice — so
    kernel-vs-oracle parity is deterministic, with no round-half fma
    jitter to absorb, and the residual is exactly zero."""
    top = (2**bits - 1) * delta
    return top * jax.random.bernoulli(key, 0.5, (N, nb, d)).astype(jnp.float32)


@pytest.mark.parametrize("scheme,group,n_out", [
    ("per_channel", None, 1), ("per_channel", 16, 1),
    ("per_token", None, 2), ("per_token", 32, 2),
    ("per_token_group", 16, 2), ("per_channel", None, 0),
])
def test_gear_compress_bit_identical_on_lattice(scheme, group, n_out, rng):
    """The fused kernel's quant/stats/outlier outputs match the
    compress_matrix pieces EXACTLY (packing bit-identical) on lattice data,
    for both orientations, grouped stats, and the no-outlier path."""
    from repro.kernels.gear_compress import gear_compress
    x = _lattice_chunks(rng, 4, 32, 64)
    outs_k = gear_compress(x, bits=4, scheme=scheme, group=group,
                           n_out=n_out, interpret=True)
    outs_r = ref.gear_compress_ref(x, bits=4, scheme=scheme, group=group,
                                   n_out=n_out)
    for name, a, b in zip(("packed", "scale", "zero", "sp_val", "sp_idx",
                           "resid"), outs_k, outs_r):
        if b is None:
            assert a is None, name
            continue
        assert (jnp.asarray(a) == jnp.asarray(b)).all(), name
    # lossless lattice => zero residual => zero low-rank factors downstream
    assert (outs_k[5] == 0).all()


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("scheme,n_out", [("per_channel", 1), ("per_token", 2)])
def test_gear_compress_gaussian_jitter_bounded(bits, scheme, n_out, rng):
    """On arbitrary data the kernel and the oracle are separately-compiled
    programs: codes may flip ±1 on round-half boundaries (≪0.1% of entries,
    same budget as quant_pack), stats and outliers stay exact."""
    from repro.core import packing
    from repro.kernels.gear_compress import gear_compress
    x = jax.random.normal(rng, (4, 32, 64))
    pk, sk, zk, svk, sik, rk = gear_compress(x, bits=bits, scheme=scheme,
                                             n_out=n_out, interpret=True)
    pr, sr, zr, svr, sir, rr = ref.gear_compress_ref(x, bits=bits,
                                                     scheme=scheme, n_out=n_out)
    assert jnp.allclose(sk, sr) and jnp.allclose(zk, zr)
    assert (sik == sir).all() and jnp.allclose(svk, svr)
    diff = jnp.abs(packing.unpack(pk, bits, 64) - packing.unpack(pr, bits, 64))
    assert int(diff.max()) <= 1
    assert float((diff > 0).mean()) < 1e-3
    # residual differs only where a code flipped, by exactly one scale step
    assert float(jnp.abs(rk - rr).max()) <= float(sk.max()) + 1e-6


def test_gear_compress_pack_roundtrip(rng):
    """Packed lanes invert through packing.unpack to in-range codes that
    reproduce the remainder within half a quantization step."""
    from repro.core import packing
    from repro.kernels.gear_compress import gear_compress
    x = jax.random.normal(rng, (2, 16, 64))
    pk, sk, zk, _, _, _ = gear_compress(x, bits=4, scheme="per_channel",
                                        n_out=0, interpret=True)
    codes = packing.unpack(pk, 4, 64)
    assert int(codes.min()) >= 0 and int(codes.max()) <= 15
    deq = codes.astype(jnp.float32) * sk + zk      # sk/zk [N, 1, d] broadcast
    assert float(jnp.abs(deq - x).max()) <= 0.5 * float(sk.max()) + 1e-5
    assert (packing.pack(codes, 4) == pk).all()


def test_gear_compress_orientations_match_cache_layout(rng):
    """Output shapes line up with the cache's per-chunk storage layout."""
    from repro.kernels.gear_compress import gear_compress
    x = jax.random.normal(rng, (3, 32, 64))
    pk, sk, zk, sv, si, r = gear_compress(x, bits=4, scheme="per_channel",
                                          group=8, n_out=1, interpret=True)
    assert pk.shape == (3, 32, 8) and sk.shape == (3, 4, 64)
    assert sv.shape == (3, 64, 2) and r.shape == (3, 32, 64)
    pk, sk, zk, sv, si, r = gear_compress(x, bits=4, scheme="per_token",
                                          group=16, n_out=2, interpret=True)
    assert sk.shape == (3, 32, 4) and sv.shape == (3, 32, 4)


# ---------------------------------------------------------------------------
# Streaming-prefill attention pieces


@pytest.mark.parametrize("T,Dh,cap", [(16, 64, 0.0), (32, 128, 0.0), (16, 64, 20.0)])
def test_flash_prefill_block_sweep(T, Dh, cap, rng):
    from repro.kernels.flash_prefill import flash_prefill_block
    q = jax.random.normal(rng, (4, T, Dh))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (4, T, Dh))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (4, T, Dh))
    kv_len = jnp.asarray([T, T // 2, 1, 0], jnp.int32)   # full/partial/one/empty
    a_k, m_k, l_k = flash_prefill_block(q, k, v, kv_len, scale=Dh**-0.5,
                                        softcap=cap, interpret=True)
    a_r, m_r, l_r = ref.flash_block_ref(q, k, v, kv_len, scale=Dh**-0.5,
                                        softcap=cap)
    assert jnp.allclose(m_k[..., 0], m_r, atol=1e-5)
    assert jnp.allclose(l_k[..., 0], l_r, atol=1e-4)
    assert jnp.allclose(a_k, a_r, atol=1e-4)


def test_gear_hist_block_ref_matches_gear_decode_ref(rng):
    """The streaming history scorer (densified fast path) and the decode
    oracle (factored path) are the same math."""
    cfg, common, extras = _cache_arrays("gear_kcvt4", Dh=64, S=128, n=128, nb=32)
    arrays = common[:-1]
    q = jax.random.normal(rng, (4, 48, 64))     # block of G*T query rows
    kwargs = dict(bits=4, chunk=32, scale_factor=64**-0.5)
    for n_comp in (jnp.int32(0), jnp.int32(64), jnp.asarray([0, 32, 96, 128])):
        acc_a, m_a, l_a = ref.gear_decode_ref(q, *arrays, n_comp, **kwargs, **extras)
        acc_b, m_b, l_b = ref.gear_hist_block_ref(q, *arrays, n_comp, **kwargs, **extras)
        assert jnp.allclose(m_a, m_b, atol=1e-4)
        assert jnp.allclose(l_a, l_b, rtol=1e-5, atol=1e-4)
        mask = l_a[..., None] > 1e-20
        assert jnp.allclose(jnp.where(mask, acc_a, 0), jnp.where(mask, acc_b, 0),
                            rtol=1e-4, atol=1e-3)


def test_gear_attend_block_kernel_matches_oracle(rng):
    """The full streaming attention step — gear_decode history + flash
    block + two-piece merge — agrees between forced-interpret kernels and
    the jnp oracles."""
    import dataclasses as dc
    from repro.core import CacheConfig as CC
    from repro.core import named_policy as np_
    from repro.core import init_layer_cache as ilc, prefill_layer_cache as plc
    from repro.kernels import ops as kernel_ops
    pol = dc.replace(np_("gear_kcvt4"), buffer_size=16)
    cfg = CC(batch=2, kv_heads=2, head_dim=64, capacity=64, policy=pol)
    k = jax.random.normal(rng, (2, 2, 48, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 1), (2, 2, 48, 64))
    cache = plc(cfg, ilc(cfg), k, v)
    q = jax.random.normal(jax.random.fold_in(rng, 2), (2, 4, 16, 64))
    k_blk = jax.random.normal(jax.random.fold_in(rng, 3), (2, 2, 16, 64))
    v_blk = jax.random.normal(jax.random.fold_in(rng, 4), (2, 2, 16, 64))
    for n_comp, blk_len in ((32, 16), (0, 16), (48, 5)):
        o_ref = kernel_ops.gear_attend_block(cfg, cache, q, k_blk, v_blk,
                                             n_comp, blk_len, 64**-0.5)
        o_krn = kernel_ops.gear_attend_block(cfg, cache, q, k_blk, v_blk,
                                             n_comp, blk_len, 64**-0.5,
                                             force_kernel=True, interpret=True)
        valid = o_ref[:, :, :blk_len]
        assert jnp.allclose(o_krn[:, :, :blk_len], valid, atol=1e-4), (n_comp, blk_len)


def test_attention_train_flash_impl_matches_chunked(rng):
    """Satellite: the monolithic full-sequence path dispatches through the
    flash_prefill kernel (interpret mode here) and agrees with the scanned
    XLA blocks within bf16 score resolution — causal, windowed, and
    softcapped variants."""
    import dataclasses as dc
    from repro.configs.base import ModelConfig
    from repro.models import attention as attn_lib
    from repro.models.common import KeyGen
    base = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=64)
    cases = [
        (base, "global"),
        (dc.replace(base, attn_pattern="local_global", local_window=8), "local"),
        (dc.replace(base, attn_logit_softcap=20.0), "global"),
    ]
    for cfg, kind in cases:
        params = attn_lib.attn_params(cfg, KeyGen(jax.random.PRNGKey(0)))
        x = jax.random.normal(rng, (2, 48, 64), jnp.bfloat16)
        pos = jnp.arange(48, dtype=jnp.int32)
        out_c, (k_c, v_c) = attn_lib.attention_train(cfg, params, x, pos, kind)
        out_f, (k_f, v_f) = attn_lib.attention_train(cfg, params, x, pos, kind,
                                                     impl="flash-interpret")
        assert (k_c == k_f).all() and (v_c == v_f).all()   # same projections
        assert jnp.allclose(out_c.astype(jnp.float32), out_f.astype(jnp.float32),
                            atol=3e-2), kind


def test_flash_prefill_kv_repeat_matches_broadcast(rng):
    """GQA via the kv_repeat index map == explicitly broadcast K/V."""
    q = jax.random.normal(rng, (8, 64, 64), jnp.float32)        # B*Hkv*G = 8
    k = jax.random.normal(jax.random.fold_in(rng, 1), (4, 64, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (4, 64, 64))
    o_map = flash_prefill(q, k, v, bq=32, bk=32, kv_repeat=2, interpret=True)
    kb = jnp.repeat(k, 2, axis=0)
    vb = jnp.repeat(v, 2, axis=0)
    o_rep = flash_prefill(q, kb, vb, bq=32, bk=32, interpret=True)
    assert jnp.allclose(o_map, o_rep, atol=1e-6)
