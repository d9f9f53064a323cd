"""Wrappers over jax's mesh / sharding API surface (jax 0.9).

Every mesh- or sharding-sensitive call in the codebase funnels through this
module — one choke point instead of scattered ``jax.sharding.*`` lookups,
so a future jax that moves one of these surfaces changes one file:

* :func:`make_mesh` — mesh construction with ``Auto`` axis types (the
  repo's sharding is constraint-driven);
* :func:`ambient_mesh` — jax's own currently active (abstract) mesh;
* :func:`native_mesh_scope` — activate a mesh with ``jax.set_mesh``;
* :func:`with_sharding_constraint` — constraint application that degrades
  to a no-op when no mesh is reachable instead of raising;
* :func:`cost_analysis` — ``Compiled.cost_analysis()`` as a dict;
* :func:`shard_map` / :func:`pjit` — stable entry points for the
  transforms.

Higher-level mesh threading (the explicit :class:`~repro.compat.meshctx.\
MeshContext`) lives in ``repro.compat.meshctx`` on top of these.
"""
from __future__ import annotations

import contextlib
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Sequence[Any] | None = None,
) -> Mesh:
    """Build a device mesh with ``Auto`` axis types."""
    names = tuple(axis_names)
    kwargs: dict[str, Any] = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(
        tuple(int(s) for s in axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names), **kwargs)


def ambient_mesh():
    """jax's own currently active mesh (set by ``jax.set_mesh``), or
    ``None``.  This is the *fallback* discovery path — explicit
    ``MeshContext`` threading (repro.compat.meshctx) is the primary one."""
    m = jax.sharding.get_abstract_mesh()
    if m is not None and not m.empty:
        return m
    return None


def native_mesh_scope(mesh):
    """Context manager activating ``mesh`` with ``jax.set_mesh`` (``None``
    gets a null scope)."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def with_sharding_constraint(x, spec, mesh=None):
    """``jax.lax.with_sharding_constraint`` that cannot crash for want of
    a mesh.

    * ``NamedSharding`` specs pass straight through.
    * With a concrete :class:`Mesh` (given or ambient) the spec is bound
      into a ``NamedSharding``.
    * With only an abstract mesh, the bare spec is used.
    * With no mesh at all the constraint is an identity, so single-device
      smoke paths never pay for distribution plumbing.
    """
    if isinstance(spec, NamedSharding):
        return jax.lax.with_sharding_constraint(x, spec)
    if mesh is None:
        mesh = ambient_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return x
    if isinstance(mesh, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty where the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def shard_map(f, mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map`` with the mesh passed explicitly."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


def pjit(fun, **kwargs):
    """Partitioned jit entry point: ``jax.jit`` takes in/out_shardings;
    kept as a named entry point so call sites survive a future split."""
    return jax.jit(fun, **kwargs)


__all__ = [
    "make_mesh",
    "ambient_mesh",
    "native_mesh_scope",
    "with_sharding_constraint",
    "cost_analysis",
    "shard_map",
    "pjit",
    "Mesh",
    "NamedSharding",
    "P",
]
