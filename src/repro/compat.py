"""Shared adapters over the installed jax (0.9.0).

  * ``make_mesh`` — `jax.make_mesh` builds meshes whose axes are Explicit
    by default. The model, training and pipeline code is written for Auto
    axes (sharding propagated by the compiler inside ``with mesh:``), so
    every mesh in the repo is built through this helper.
  * jaxpr introspection (``Jaxpr``/``ClosedJaxpr`` from ``jax.extend.core``)
    plus the nested-jaxpr walkers the perf-invariant tests share.
  * ``jit_cache_size`` — the number of compiled programs behind a jitted
    function (a private accessor; the one-XLA-program gates count with it).
"""
from __future__ import annotations

from typing import Iterator, Sequence, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.sharding import AxisType

# ---------------------------------------------------------------------------
# meshes
# ---------------------------------------------------------------------------


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]
              ) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis Auto (compiler-propagated sharding)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


# ---------------------------------------------------------------------------
# jit cache introspection
# ---------------------------------------------------------------------------


def jit_cache_size(fn) -> int:
    """Number of distinct compiled programs behind a jitted function."""
    return fn._cache_size()


# ---------------------------------------------------------------------------
# jaxpr introspection
# ---------------------------------------------------------------------------


def sub_jaxprs(value) -> list:
    """All jaxprs hiding inside an eqn param value (list/tuple/closed)."""
    if isinstance(value, ClosedJaxpr):
        return [value.jaxpr]
    if isinstance(value, Jaxpr):
        return [value]
    if isinstance(value, (list, tuple)):
        return [j for v in value for j in sub_jaxprs(v)]
    return []


def walk_primitives(jaxpr, in_cond: bool = False
                    ) -> Iterator[Tuple[str, bool]]:
    """Yield (primitive_name, inside_cond_branch) over all nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, in_cond
        child_in_cond = in_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from walk_primitives(sub, child_in_cond)
