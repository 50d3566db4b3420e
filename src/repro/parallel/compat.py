"""The library's shard_map and mesh spellings, in one place.

Every shard_map/mesh construction in the library and the distributed tests
routes through this module, so the choices below are made once:

* the replication check is off — the FOEM collectives produce
  deliberately device-varying intermediates that the checker rejects;
* every mesh axis is ``AxisType.Auto``, pinned explicitly so a change of
  JAX's default axis type cannot flip the library's collectives.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
from jax.sharding import AbstractMesh, Mesh


def shard_map(f=None, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check`` mapped to ``check_vma``.

    Usable directly or as a decorator (``f=None``).
    """
    if f is None:
        return functools.partial(
            shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check=check,
        )
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check,
    )


def pvary(x, axis_name):
    """Mark ``x`` device-varying over ``axis_name`` (``lax.pcast``)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        **kwargs,
    )


def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """Device-free mesh for sharding-rule unit tests."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))
