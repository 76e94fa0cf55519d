"""Partially manual ``shard_map``.

The train loop runs PowerSGD under a *partially manual* ``shard_map``: the
``pod`` axis is manual (the compressor issues explicit ``pmean`` over it)
while ``data``/``model`` stay with the SPMD partitioner.
"""

from __future__ import annotations

from typing import Iterable

import jax

__all__ = ["manual_shard_map"]


def manual_shard_map(fn, mesh, in_specs, out_specs, manual_axes: Iterable[str]):
    """``shard_map(fn)`` manual over ``manual_axes``, auto over the rest."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(manual_axes), check_vma=False)
