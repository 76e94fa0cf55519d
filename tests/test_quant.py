"""Unit tests: packing, quantization backbones, outlier filter, power iteration."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import packing, quant, outlier, lowrank


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(3, 5, 64), (2, 128), (1, 1, 1, 32)])
def test_pack_roundtrip(bits, shape, rng):
    codes = jax.random.randint(rng, shape, 0, 2**bits)
    assert (packing.unpack(packing.pack(codes, bits), bits, shape[-1]) == codes).all()


def test_pack_rejects_bad_width():
    with pytest.raises(ValueError):
        packing.pack(jnp.zeros((4, 7), jnp.int32), 2)
    with pytest.raises(ValueError):
        packing.codes_per_lane(3)


@pytest.mark.parametrize("scheme,group", [
    ("per_token_group", 32), ("per_channel", None), ("per_channel", 16),
    ("per_token", None), ("per_token", 32),
])
def test_quant_8bit_accurate(scheme, group, rng):
    x = jax.random.normal(rng, (2, 64, 64))
    qt = quant.quantize(x, 8, scheme, group)
    err = jnp.linalg.norm(x - quant.dequantize(qt)) / jnp.linalg.norm(x)
    assert err < 0.01


@pytest.mark.parametrize("jit", [False, True])
def test_round_to_dtype_is_bf16_rounding(jit, rng):
    """The int32-view rounding equals a real bf16 store bit for bit, ties
    to even included, eagerly and inside one program."""
    x = jax.random.normal(rng, (4096,)) * 10.0 ** jnp.arange(-4, 4).repeat(512)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    ties = jax.lax.bitcast_convert_type((bits & -0x10000) | 0x8000, jnp.float32)
    x = jnp.concatenate([x, ties, -ties, jnp.zeros(1)])
    fn = jax.jit(quant.round_to_dtype, static_argnums=1) if jit else quant.round_to_dtype
    want = x.astype(jnp.bfloat16).astype(jnp.float32)
    got = fn(x, jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert (jax.lax.bitcast_convert_type(got, jnp.int32)
            == jax.lax.bitcast_convert_type(want, jnp.int32)).all()
    assert (fn(x, jnp.float32) == x).all()


def test_quant_monotone_in_bits(rng):
    x = jax.random.normal(rng, (4, 128, 64))
    errs = [float(quant.quant_error(x, b, "per_channel")) for b in (2, 4, 8)]
    assert errs[0] > errs[1] > errs[2]


def test_quant_constant_group_safe():
    x = jnp.ones((2, 16, 32))
    qt = quant.quantize(x, 4, "per_token")
    assert jnp.allclose(quant.dequantize(qt), x, atol=1e-5)


@pytest.mark.parametrize("axis", ["token", "channel"])
def test_outlier_split_exact(axis, rng):
    x = jax.random.normal(rng, (3, 32, 16))
    sp, rem = outlier.filter_outliers(x, 0.1, axis)
    assert jnp.allclose(rem + outlier.densify(sp), x, atol=1e-6)
    # removed entries are the extremes: remainder range is within original
    assert float(jnp.abs(rem).max()) <= float(jnp.abs(x).max())


def test_outlier_reduces_dynamic_range(rng):
    x = jax.random.normal(rng, (2, 64, 32))
    x = x.at[:, 0, 0].set(100.0)
    _, rem = outlier.filter_outliers(x, 0.05, "token")
    assert float(jnp.abs(rem).max()) < 50.0


def test_power_iteration_matches_svd(rng):
    u = jax.random.normal(rng, (2, 64, 6))
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 6))
    x = u @ jnp.swapaxes(v, -1, -2)
    approx = lowrank.lowrank_approx(x, 6, iters=8)
    exact = lowrank.svd_topr(x, 6)
    assert float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact)) < 1e-3


def test_power_iteration_error_decreases_with_rank(rng):
    x = jax.random.normal(rng, (1, 96, 48))
    errs = []
    for r in (1, 4, 16):
        a = lowrank.lowrank_approx(x, r, iters=6)
        errs.append(float(jnp.linalg.norm(x - a)))
    assert errs[0] > errs[1] > errs[2]
