"""Thin helpers over the installed JAX (0.9.0) that several modules share.

* :func:`make_mesh` — ``jax.make_mesh`` with every axis ``AxisType.Auto``
  unless the caller says otherwise: the repo's logical sharding rules rely
  on the partitioner propagating shardings.
* :func:`shard_map` — ``jax.shard_map``.
* :func:`cost_analysis` — the compiled program's flat cost dict.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType


def shard_map(f, mesh=None, in_specs=None, out_specs=None, *,
              check_vma: bool = True, **kwargs: Any):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def cost_analysis(compiled) -> dict:
    return compiled.cost_analysis()


def make_mesh(shape, axes, **kwargs: Any):
    kwargs.setdefault("axis_types", (AxisType.Auto,) * len(axes))
    return jax.make_mesh(shape, axes, **kwargs)
