"""One token a slot through a layer's state, where the state lies.

Each slot's state of a layer is ``[R, N, 128]`` float32 in the pool
``[layers, slots, R, N, 128]`` (package docstring). A decode step reads it
and writes it back once: on a TPU under one device the Pallas kernel
``ssm_decode`` (one call a layer) updates the layer's rows of the donated
pool in place, ``8 x 64 KiB`` a grid step; everywhere else the same
arithmetic runs in jnp (:func:`decode_kernel_runs` is the one rule, as
``serving/paged_attention.decode_kernel_runs`` is for the KV walk).

With ``u = dt x`` and ``g = exp(dt A)`` spread over a head's channels,
a packed row of 128 channels is ``S <- g S + B u`` (``B`` down the
sublanes, ``g`` and ``u`` along the lanes) and ``y = sum_n C_n S_n``, a
sum down the sublanes: whole-vreg products and no relayout of the state.
A slot that is not live keeps its state as it was; a live slot whose
token is at position 0 starts from zero, whatever its row held.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._platform import interpret as _interpret
from deepspeed_tpu.ops.ssm.scan import LANES

# packed rows of a slot's state that one grid step updates (8 x 64 KiB in
# and out, double-buffered: 2 MiB of VMEM)
_ROWS = 8


def decode_kernel_runs() -> bool:
    """Whether :func:`decode_update` is the Pallas kernel here: on a TPU,
    under no multi-device mesh (GSPMD refuses a bare ``pallas_call``)."""
    from deepspeed_tpu.utils import groups
    return not _interpret() and not (groups.mesh_is_initialized()
                                     and groups.get_mesh().size > 1)


def _lanes(v):
    """``[B, C]`` channels as ``[B, R, 128]`` rows, pad channels 0."""
    B, C = v.shape
    R = -(-C // LANES)
    return jnp.pad(v, ((0, 0), (0, R * LANES - C))).reshape(B, R, LANES)


def group_rows(rows: int, groups: int) -> int:
    """Packed rows of a slot's state that one group of ``B`` and ``C``
    covers: a group's heads fill whole 128-lane rows (the caller refuses a
    model whose do not)."""
    if rows % groups:
        raise ValueError(f"{groups} groups of B and C do not divide the "
                         f"{rows} packed rows of a slot's state")
    return rows // groups


def step(state, b, c, decay, u, live, fresh):
    """The jnp form: ``state [B, R, N, 128]``, ``b``/``c`` ``[B, G, N]``
    (packed row ``r`` reads group ``r // (R / G)``), ``decay``/``u``
    ``[B, R, 128]``, ``live``/``fresh`` ``[B]`` bool. Returns
    ``y [B, R, 128]`` and the state after the token, in the state's dtype
    (the arithmetic is float32)."""
    per = group_rows(state.shape[1], b.shape[1])
    b, c = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)
    old = state.astype(jnp.float32)
    s = jnp.where(fresh[:, None, None, None], 0.0, old)
    new = decay[:, :, None, :] * s + b[:, :, :, None] * u[:, :, None, :]
    y = jnp.sum(new * c[:, :, :, None], axis=2)
    return y, jnp.where(live[:, None, None, None], new,
                        old).astype(state.dtype)


def _kernel(layer_ref, live_ref, fresh_ref, b_ref, c_ref, decay_ref, u_ref,
            s_ref, y_ref, out_ref, *, per):
    """Grid program ``(slot, r)``: packed rows ``r*Rb ..`` of the slot's
    state (``s_ref [Rb, N, 128]``, aliased to ``out_ref``) and the groups
    of ``B`` and ``C`` they read (``b_ref``/``c_ref [1, g, 1, N]``; row
    ``j`` reads group ``j // per`` of them)."""
    slot = pl.program_id(0)
    live = live_ref[slot] != 0
    fresh = fresh_ref[slot] != 0
    N, L = s_ref.shape[1:]
    # B and C down the sublanes, the same in every lane
    cols = [(jnp.broadcast_to(b_ref[0, g], (L, N)).T,
             jnp.broadcast_to(c_ref[0, g], (L, N)).T)
            for g in range(b_ref.shape[1])]
    for j in range(s_ref.shape[0]):
        b_col, c_col = cols[j // per]
        old = s_ref[j].astype(jnp.float32)
        s = jnp.where(fresh, 0.0, old)
        new = decay_ref[0, j:j + 1, :] * s + b_col * u_ref[0, j:j + 1, :]
        y_ref[0, j:j + 1, :] = jnp.sum(new * c_col, axis=0, keepdims=True)
        out_ref[j] = jnp.where(live, new, old).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(pool, layer, b, c, decay, u, live, fresh, interpret=False):
    """:func:`_kernel` over every slot of pool layer ``layer`` (a traced
    scalar: one traced and lowered kernel for all the layers of a
    program). A grid step's groups come as a block of ``[1, g, 1, N]``:
    the group's own ``[1, N]`` row is whole, so any ``g`` tiles."""
    _, B, R, N, L = pool.shape
    G = b.shape[1]
    per = group_rows(R, G)
    rows = _ROWS if R % _ROWS == 0 else R
    if rows % per and per % rows:
        rows = R
    g_block = max(1, rows // per)
    state_spec = pl.BlockSpec((None, None, rows, N, L),
                              lambda s, r, layer, *_: (layer[0], s, r, 0, 0))
    vec_spec = pl.BlockSpec(
        (1, g_block, 1, N),
        lambda s, r, *_: (s, r * rows // per // g_block, 0, 0))
    row_spec = pl.BlockSpec((1, rows, L), lambda s, r, *_: (s, r, 0))
    return pl.pallas_call(
        functools.partial(_kernel, per=min(per, rows)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, R // rows),
            in_specs=[vec_spec, vec_spec, row_spec, row_spec, state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, R, L), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (after the three prefetched) is the pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.astype(jnp.int32),
      fresh.astype(jnp.int32), b[:, :, None, :], c[:, :, None, :], decay, u,
      pool)


def decode_update(pool, layer, x, b, c, dt, a, live, fresh):
    """One token for every slot of pool layer ``layer``: x ``[B, H, P]``,
    b and c ``[B, G, N]`` (head ``h`` reads group ``h // (H / G)``), dt
    ``[B, H]`` (after its softplus), a ``[H]``, all float32; live and
    fresh ``[B]`` bool. Returns ``y [B, H, P]`` (no ``D`` skip) and the
    pool with the layer's rows updated."""
    B, H, P = x.shape
    u = _lanes((dt[:, :, None] * x).reshape(B, H * P))
    decay = _lanes(jnp.repeat(jnp.exp(dt * a), P, axis=1))
    fresh = fresh & live
    if decode_kernel_runs():
        y, pool = _decode_call(pool, layer, b, c, decay, u, live, fresh)
    else:
        y, new = step(pool[layer], b, c, decay, u, live, fresh)
        pool = pool.at[layer].set(new)
    return y.reshape(B, -1)[:, :H * P].reshape(B, H, P), pool
