"""Sharding helpers: NamedSharding rules + shard_map plumbing.

The reference has no sharding notion — its unit is "a named tensor,
replicated everywhere, allreduced on demand".  On TPU the idiomatic
equivalent is: put arrays in the right :class:`NamedSharding` and let
XLA insert collectives.  These helpers centralize that.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicate(mesh: Mesh, tree: Any) -> Any:
    """Replicate a pytree across the whole mesh — the SPMD analog of
    `broadcast_parameters` (reference `torch/functions.py:30`): afterwards
    every device holds identical values."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def batch_sharding(mesh: Mesh, batch_axes: Union[str, Sequence[str]] = "data",
                   ndim: int = 2) -> NamedSharding:
    """Shard dim 0 (batch) over the data axis, replicate the rest."""
    spec = [batch_axes] + [None] * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def shard_batch(mesh: Mesh, batch: Any,
                batch_axes: Union[str, Sequence[str]] = "data") -> Any:
    """Place host batch arrays so dim 0 is split across the data axis —
    what the per-rank data loader achieves in the reference by each rank
    reading its own shard."""
    def _put(x):
        spec = [batch_axes] + [None] * (x.ndim - 1)
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree_util.tree_map(_put, batch)


def shard_map_fn(fn, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with this repo's default of ``check_vma=False``."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
