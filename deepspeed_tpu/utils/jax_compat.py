"""One home for jax APIs whose spelling has moved between releases, so a
rename is fixed once instead of at every call site. Written for the
installed jax (0.9): no branch for any other version."""

from jax import shard_map
from jax.sharding import AxisType, get_abstract_mesh


def get_shard_map():
    """(shard_map, kwargs): the callable plus the replication-check-off
    keyword (``check_vma=False``)."""
    return shard_map, {"check_vma": False}


def under_manual_sharding():
    """True when tracing INSIDE a shard_map body (the abstract mesh has
    Manual axes) — a nested shard_map over the same axes would crash at
    trace time, so mesh-aware wrappers must no-op there."""
    return AxisType.Manual in get_abstract_mesh().axis_types
