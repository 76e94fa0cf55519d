"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode on the CPU cannot see what the TPU compiler (Mosaic) refuses:
block shapes off the tiling, casts it has no lowering for, reshapes that
split lanes.  These tests compile each kernel of the serving path for a
*described* v5e chip (no chip attached) at real widths — minicpm-2b's
(head_dim 64, 36 kv heads) and head_dim 128 — with the GEAR-KCVT-4bit cache
geometry of an 8-slot, 1088-token engine, and the decode kernels also at
starcoder2-3b's GQA shape (12 query rows per kv head, 2 kv heads of 128,
16 slots of 2048 tokens).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cache import CacheConfig, init_layer_cache, page_field_shapes
from repro.core.outlier import outlier_count
from repro.core.policy import named_policy
from repro.kernels.flash_prefill import flash_prefill, flash_prefill_block
from repro.kernels.gear_compress import gear_compress
from repro.kernels.gear_decode import gear_decode, gear_decode_paged

POL = named_policy("gear_kcvt4")
NB = POL.buffer_size
SLOTS, CAPACITY = 8, 1088
WIDTHS = [(64, 36), (128, 8)]          # (head_dim, kv heads)
ORIENTATIONS = ["k", "v"]
# decode kernels: (head_dim, kv heads, query rows per kv head, slots,
# capacity); the last is starcoder2-3b's GQA decode at the benchmark's size
DECODE_SHAPES = [
    *(pytest.param(dh, heads, 1, SLOTS, CAPACITY, id=f"{dh}-{heads}")
      for dh, heads in WIDTHS),
    pytest.param(128, 2, 12, 16, 2048, id="gqa12-128-2"),
]


@pytest.fixture(scope="module")
def topo():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **kwargs) -> str:
    text = jax.jit(fn).lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"
    return text


def _flat_operands(leaves: dict, rows: int, sharding) -> dict:
    """Cache leaves [A, H, ...] -> kernel rows [A*H, ...] as abstract arrays."""
    return {name: jax.ShapeDtypeStruct((rows,) + leaf.shape[2:], leaf.dtype,
                                       sharding=sharding)
            for name, leaf in leaves.items() if leaf is not None}


def _decode_operands(cfg: CacheConfig, sharding):
    cache = jax.eval_shape(lambda: init_layer_cache(cfg))
    names = ("k_packed", "k_scale", "k_zero", "v_packed", "v_scale", "v_zero",
             "k_a", "k_b", "v_a", "v_b",
             "k_sp_val", "k_sp_idx", "v_sp_val", "v_sp_idx")
    return _flat_operands({n: getattr(cache, n) for n in names},
                          cfg.batch * cfg.kv_heads, sharding)


def _split(ops: dict):
    head = [ops.pop(n) for n in ("k_packed", "k_scale", "k_zero",
                                 "v_packed", "v_scale", "v_zero")]
    return head, ops


@pytest.mark.parametrize("dh,heads,g,slots,capacity", DECODE_SHAPES)
def test_gear_decode_compiles(one_chip, dh, heads, g, slots, capacity):
    cfg = CacheConfig(batch=slots, kv_heads=heads, head_dim=dh,
                      capacity=capacity, policy=POL)
    bh = slots * heads
    head, extras = _split(_decode_operands(cfg, one_chip))
    q = jax.ShapeDtypeStruct((bh, g, dh), jnp.float32, sharding=one_chip)
    n_comp = jax.ShapeDtypeStruct((bh,), jnp.int32, sharding=one_chip)
    _compile(lambda q, head, n, extras: gear_decode(
        q, *head, n, bits=POL.bits, chunk=NB, scale_factor=dh**-0.5, **extras),
        q, head, n_comp, extras)


@pytest.mark.parametrize("dh,heads,g,slots,capacity", DECODE_SHAPES)
def test_gear_decode_paged_compiles(one_chip, dh, heads, g, slots, capacity):
    cfg = CacheConfig(batch=1, kv_heads=heads, head_dim=dh,
                      capacity=capacity, policy=POL)
    pages = slots * cfg.n_chunks + 1
    pool = {n: None if spec is None
            else jax.ShapeDtypeStruct((pages,) + spec[0], spec[1])
            for n, spec in page_field_shapes(cfg).items()}
    head, extras = _split(_flat_operands(pool, pages * heads, one_chip))
    bh = slots * heads
    q = jax.ShapeDtypeStruct((bh, g, dh), jnp.float32, sharding=one_chip)
    n_comp = jax.ShapeDtypeStruct((bh,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((slots, cfg.n_chunks), jnp.int32,
                                  sharding=one_chip)
    _compile(lambda q, head, n, bt, extras: gear_decode_paged(
        q, *head, n, bt, bits=POL.bits, chunk=NB, scale_factor=dh**-0.5,
        **extras), q, head, n_comp, tables, extras)


@pytest.mark.parametrize("kind", ORIENTATIONS)
@pytest.mark.parametrize("dh,heads", WIDTHS)
def test_gear_compress_compiles(one_chip, dh, heads, kind):
    """One streaming-prefill compression event: every head's chunk."""
    scheme, group = POL.scheme_for(kind)
    n_out = outlier_count(NB if scheme == "per_channel" else dh, POL.sparsity)
    x = jax.ShapeDtypeStruct((heads, NB, dh), jnp.float32, sharding=one_chip)
    _compile(lambda x: gear_compress(x, bits=POL.bits, scheme=scheme,
                                     group=group, n_out=n_out), x)


@pytest.mark.parametrize("dh,heads", WIDTHS)
def test_flash_prefill_compiles(one_chip, dh, heads):
    """Monolithic prefill attention of a 1024-token prompt."""
    s = jax.ShapeDtypeStruct((heads, 1024, dh), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_prefill(q, k, v, bq=128, bk=128), s, s, s)


@pytest.mark.parametrize("dh,heads", WIDTHS)
def test_flash_prefill_block_compiles(one_chip, dh, heads):
    """Streaming prefill: one chunk's queries against its own block."""
    s = jax.ShapeDtypeStruct((heads, NB, dh), jnp.float32, sharding=one_chip)
    n = jax.ShapeDtypeStruct((heads,), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, n: flash_prefill_block(q, k, v, n,
                                                    scale=dh**-0.5), s, s, s, n)
