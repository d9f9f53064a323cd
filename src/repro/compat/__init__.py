"""jax mesh / sharding compatibility layer.

Everything in the repo that touches a mesh- or sharding-sensitive jax
surface — mesh construction, current-mesh discovery, mesh activation,
sharding constraints, ``shard_map``/``pjit`` — goes through this package.
See ``jaxshim`` for the low-level wrappers and ``meshctx`` for the explicit
:class:`MeshContext` threading that replaced the seed's implicit
``get_abstract_mesh()`` global lookups.

Supported: the one installed jax, 0.9 (pinned in ``requirements.txt``).
"""
from repro.compat.jaxshim import (
    ambient_mesh,
    cost_analysis,
    make_mesh,
    native_mesh_scope,
    pjit,
    shard_map,
    with_sharding_constraint,
)
from repro.compat.meshctx import (
    NULL_MESH_CONTEXT,
    MeshContext,
    concrete_mesh,
    current_mesh_context,
    use_mesh,
)

__all__ = [
    "make_mesh",
    "ambient_mesh",
    "native_mesh_scope",
    "with_sharding_constraint",
    "cost_analysis",
    "shard_map",
    "pjit",
    "MeshContext",
    "NULL_MESH_CONTEXT",
    "concrete_mesh",
    "current_mesh_context",
    "use_mesh",
]
