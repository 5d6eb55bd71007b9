"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "make_search_mesh",
           "validate_search_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU tests/examples (same axis names)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_search_mesh(n_devices: int | None = None):
    """1-D ``("search",)`` mesh for the index query planner.

    Both planner strategies run over this axis: query-sharded search
    splits the padded batch across it (index replicated), list-sharded
    search splits the sealed inverted lists across it (queries
    replicated, partial top-k fanned in with an ``all_gather``).
    Degenerates to a 1-device mesh on CPU, where the planner's shard_map
    path is bit-identical to the plain vmap path.

    The axis is ``Auto``: the planner slices and pads its eager outputs
    outside ``shard_map``, which an ``Explicit`` axis (``jax.make_mesh``'s
    default) would refuse with a sharding-type error."""
    n = n_devices if n_devices is not None else len(jax.devices())
    return jax.make_mesh((n,), ("search",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def validate_search_mesh(mesh, n_shards: int) -> None:
    """Reject a mesh whose ``search`` axis disagrees with a data-partition
    count ``n_shards`` — a clear error at plan time instead of a shape
    error inside ``shard_map``."""
    if "search" not in mesh.shape:
        raise ValueError(
            f"expected a 1-D ('search',) mesh, got axes {mesh.axis_names}")
    n_dev = mesh.shape["search"]
    if n_shards != n_dev:
        raise ValueError(
            f"index layout is sealed for n_shards={n_shards} but the mesh "
            f"has {n_dev} devices on its 'search' axis — reseal the index "
            f"(IndexConfig(n_shards={n_dev}) + compact()) or build the "
            f"mesh with make_search_mesh({n_shards})")
