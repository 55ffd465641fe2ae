"""Run a per-(batch, head) Pallas kernel under the active device mesh.

GSPMD cannot partition a ``pallas_call``: under a multi-device mesh jax
raises "Mosaic kernels cannot be automatically partitioned. Please wrap
the call in a shard_map" (jax 0.9, four-chip v5e host — the dense flash
kernel refused every dp>1 / mp>1 layout until it was wrapped). Attention
kernels are independent per (batch, head), so batch shards over the
data-parallel axes and heads over the model axis map exactly.
"""

from jax.sharding import PartitionSpec as P

_replicate_warned = set()


def map_over_mesh(fn, batch, heads=None):
    """``fn(q, k, v, *rest)`` -> the same callable shard_mapped over the
    global mesh: q/k/v ``[B, H, S, D]`` split on batch over the
    data-parallel axes and — when ``heads`` is given — on heads over the
    model axis; ``rest`` are ``[B, ...]`` arrays (or ``None``) split on
    batch alone. A dimension that does not divide its axes stays whole:
    the kernel then runs replicated across them (warned once — e.g.
    sequence-parallel configs that borrow the data axis). Pass
    ``heads=None`` for kernels whose per-head tables are built for the
    full head count.

    Returns ``fn`` itself without a mesh, on a one-device mesh, or when
    already tracing inside a shard_map body (1-bit / sparse-grad step
    fns, ring attention): a nested shard_map over the same axes crashes
    at trace time."""
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.jax_compat import (get_shard_map,
                                                under_manual_sharding)
    if not groups.mesh_is_initialized() or under_manual_sharding():
        return fn
    mesh = groups.get_mesh()
    if mesh.size == 1:
        return fn

    def axes_for(dim, axes, what):
        axes = tuple(a for a in axes if mesh.shape[a] > 1)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if n > 1 and dim % n:
            key = (what, dim, n)
            if key not in _replicate_warned:
                _replicate_warned.add(key)
                from deepspeed_tpu.utils.logging import logger
                logger.warning(
                    "pallas attention kernel: %s %d does not divide the "
                    "%d-way mesh axes %s — the kernel will run REPLICATED "
                    "across them (every device computes the whole %s)",
                    what, dim, n, axes, what)
            return None
        return axes or None

    b_axes = axes_for(batch, groups.data_parallel_axes(), "batch")
    h_axes = None if heads is None else axes_for(
        heads, (groups.MODEL_AXIS,), "head count")
    shard_map, smap_kw = get_shard_map()
    spec4 = P(b_axes, h_axes, None, None)

    def wrapped(q, k, v, *rest):
        in_specs = (spec4, spec4, spec4) + tuple(
            None if r is None else P(b_axes, *([None] * (r.ndim - 1)))
            for r in rest)
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=spec4, **smap_kw)(q, k, v, *rest)

    return wrapped
