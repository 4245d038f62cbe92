"""JAX API shims for the installed JAX (>= 0.9).

The model / sharding / roofline stack reaches a few JAX APIs whose defaults
or return shapes are easy to get wrong; they go through here so each
decision is made once:

* :func:`make_mesh` builds **Auto** mesh axes.  ``jax.make_mesh`` defaults
  to Explicit axes, on which ``with_sharding_constraint`` (the rules'
  ``constrain``) and the GSPMD-propagated gathers of the model refuse to
  run.
* :func:`cost_analysis` turns ``Compiled.cost_analysis()`` into a dict,
  empty where the backend provides nothing.
* :func:`shard_map_body` / :func:`shard_map_mesh_size` read a shard_map
  equation's params for the roofline jaxpr walker.

``shard_map`` and ``axis_size`` need no shim: call ``jax.shard_map`` and
``jax.lax.axis_size``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict ({} when XLA provides none)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend may not implement it
        return {}
    return dict(cost) if cost else {}


# ----- jaxpr-level shard_map introspection (roofline walker) -----

# primitive param key holding the body jaxpr; a search list so a rename
# only needs updating here.
_SHARD_MAP_BODY_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def shard_map_body(params: dict) -> Optional[Any]:
    """The body jaxpr of a shard_map equation's params (or None)."""
    for k in _SHARD_MAP_BODY_KEYS:
        obj = params.get(k)
        if obj is None:
            continue
        jaxpr = obj.jaxpr if hasattr(obj, "jaxpr") else obj
        if hasattr(jaxpr, "eqns"):
            return jaxpr
    return None


def shard_map_mesh_size(params: dict) -> int:
    """Total device count of a shard_map equation's mesh (concrete ``Mesh``
    or ``AbstractMesh``: both expose ``.size`` or an axis-name->size
    ``shape``)."""
    import math

    mesh = params.get("mesh")
    if mesh is None:
        return 1
    size = getattr(mesh, "size", None)
    if size:
        return int(size)
    shape = dict(getattr(mesh, "shape", {}) or {})
    return math.prod(shape.values()) if shape else 1
