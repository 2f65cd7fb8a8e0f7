"""The ambient-mesh probe.

``current_mesh`` reports the mesh of the innermost active mesh context. On
the installed JAX (0.9) the two context managers are visible through two
different probes: ``jax.set_mesh`` through the public
``jax.sharding.get_abstract_mesh``, and the ``with mesh:`` form only through
the private ``jax._src.mesh.thread_resources`` (``get_abstract_mesh`` stays
empty inside it). Both are in use — the tests and the launch layer enter
``with mesh:`` — so the helper asks both.
"""
from __future__ import annotations

import jax


def _has_manual_axes(mesh) -> bool:
    """True when any mesh axis is Manual — i.e. we are inside a shard_map
    body, where sharding constraints over those axes are invalid."""
    types = getattr(mesh, "axis_types", ())
    values = types.values() if hasattr(types, "values") else types
    return any(t == jax.sharding.AxisType.Manual for t in values)


def current_mesh():
    """The mesh of the innermost active mesh context, or ``None``.

    Tries the public ``jax.sharding.get_abstract_mesh`` (the ``set_mesh``
    context) first and then ``thread_resources`` (the ``with mesh:``
    context — see the module docstring). No active context yields ``None``,
    which ``sharding.rules.constrain`` treats as "do not constrain" (see the
    no-op unit test in tests/test_distributed.py).

    Inside a shard_map body the context mesh carries Manual axes; that is
    reported as ``None`` too — constraining over manual axes is an error.
    Callers that need concrete devices (e.g. ``search.sharded.resolve_mesh``
    placing index shards) must additionally check for a non-abstract mesh —
    the ``set_mesh`` context yields an AbstractMesh with no device list.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty and not _has_manual_axes(mesh):
        return mesh
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    if mesh.empty or _has_manual_axes(mesh):
        return None
    return mesh
