"""Random weights from the run's seed, made on the device in one jitted
call, in the type they are served in (bf16), in the served model's tree.

The tree is the decoder's parameter layout (the interface through which
weights are loaded): ``embed [V, d]``, optional ``lm_head [d, V]``,
``final_norm``, and ``blocks``: a tuple of per-pattern-position dicts
stacked over layers (``ln1``, ``attn: wq wk wv wo``, ``ln2``, ``mlp``).
Matrices are normal with std ``fan_in ** -0.5`` (fan-in is the
second-to-last axis; the embedding's is ``d``).  Norms are drawn near the
identity: an RMSNorm ``scale`` is stored as an offset from 1 (the layout
applies ``1 + scale``), a LayerNorm has ``scale`` near 1 and ``bias`` near 0,
both with std ``NORM_STD``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def key_for(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed``, also past 32 bits."""
    seed = abs(int(seed))
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, path: tuple[str, ...], shape, layernorm: bool):
    name = path[-1]
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "bias"):
        if name == "scale" and layernorm:
            return 1.0 + NORM_STD * normal()
        return NORM_STD * normal()
    fan_in = shape[-1] if name == "embed" else shape[-2]
    return normal() * fan_in ** -0.5


def make_weights(abstract, seed: int, dtype=jnp.bfloat16):
    """Weights shaped like ``abstract`` (a pytree of shape structs in the
    layout above), in ``dtype``, from ``seed``; one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [tuple(getattr(k, "key", getattr(k, "idx", None)) for k in p)
             for p, _ in flat]
    shapes = [x.shape for _, x in flat]
    # a norm dict with a bias is a LayerNorm
    ln = {p[:-1] for p in paths if p[-1] == "bias"}

    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), p, s, p[:-1] in ln)
                  .astype(dtype)
                  for i, (p, s) in enumerate(zip(paths, shapes))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key_for(seed))
