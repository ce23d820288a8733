"""JAX API drift — the single home for it.

The repo targets the installed JAX (0.9.0, the same on the TPU host).
Two things here depend on what that JAX offers:

* :func:`make_mesh` — ``jax.make_mesh`` defaults to explicit-sharding axis
  types; the repo's meshes are GSPMD meshes, so every axis is requested as
  ``AxisType.Auto``.
* :func:`collective_batching_audit` — the mesh-sharded replay engine vmaps
  a rank axis through *real* collectives inside ``shard_map``; this audits
  that every collective primitive the replay emits has a batching rule on
  the running JAX.

``shard_map`` and pytree paths are called straight from ``jax``
(``jax.shard_map``, ``jax.tree.flatten_with_path``).  Primitive *names* the
jaxpr walker must know live in :mod:`repro.core.metrics`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


#: lax collective primitives the replay comm backends can emit (DeviceComm
#: kinds → primitive names as spelled in jax internals).
_REPLAY_COLLECTIVE_PRIMS = (
    "psum", "pmax", "pmin", "all_gather", "reduce_scatter", "all_to_all",
    "ppermute",
)


def collective_batching_audit() -> list[str]:
    """Names of replay collectives *missing* a vmap batching rule.

    The mesh-sharded sweep stacks a signature group's per-rank states and
    ``vmap``-s them through ``DeviceComm`` inside ``shard_map``; that is
    only sound when every collective primitive has a batching rule (the
    rank axis is then folded into the real collective).  Returns the names
    that lack one — empty on the installed JAX, asserted by tests; a JAX
    that drops a rule fails loudly there instead of silently falling back
    to a per-rank loop.

    Deliberately pessimistic: a primitive that cannot be *found* (public
    ``jax.lax.<name>_p`` first, then the ``jax._src.lax.parallel``
    internals) is reported as missing too — "internals moved" must surface
    in the audit test, not hollow it out.  Collective rules live in
    ``batching.fancy_primitive_batchers`` (``primitive_batchers`` is a
    write-only proxy).
    """
    import jax.lax
    from jax._src.lax import parallel as _par
    from jax.interpreters import batching
    registry = batching.fancy_primitive_batchers
    missing = []
    for name in _REPLAY_COLLECTIVE_PRIMS:
        prim = getattr(jax.lax, f"{name}_p", getattr(_par, f"{name}_p", None))
        if prim is None or prim not in registry:
            missing.append(name)
    return missing
