"""The framework's one spelling of ``jax.shard_map``.

Every ``shard_map`` user (GPipe pipeline, ring attention, the composed-mesh
train step) routes through :func:`shard_map` here so each can hand the
installed :class:`~mxnet_tpu.parallel.mesh.GraftMesh` straight through.
"""

from __future__ import annotations


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` over a raw ``jax.sharding.Mesh`` or a
    :class:`~mxnet_tpu.parallel.mesh.GraftMesh` (unwrapped here)."""
    import jax

    return jax.shard_map(f, mesh=getattr(mesh, "mesh", mesh),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)
